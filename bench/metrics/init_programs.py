"""init_programs: device programs launched by one strategy init (for ucfl
the Eq. 9 collaboration round and the state slab): the launches inside
the program's ``fl.init`` spans of the traced trial over their count. The
eager init launches one program per operation. No such span or no
launches in the trace: no reading."""

from bench import stages

SPAN = "fl.init"


def read(run):
    red = stages.of(run)
    if red is None or SPAN not in red["programs_by_span"]:
        return None
    return red["programs_by_span"][SPAN] / red["spans"][SPAN][0]
