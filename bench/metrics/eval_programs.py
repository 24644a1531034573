"""eval_programs: device programs launched in one evaluation pass: the
launches inside the program's ``fl.evaluate`` spans of the traced trial
over their count. Eager evaluation launches one program per operation; a
jitted pass would launch one. No such span or no launches in the trace:
no reading."""

from bench import stages

SPAN = "fl.evaluate"


def read(run):
    red = stages.of(run)
    if red is None or SPAN not in red["programs_by_span"]:
        return None
    return red["programs_by_span"][SPAN] / red["spans"][SPAN][0]
