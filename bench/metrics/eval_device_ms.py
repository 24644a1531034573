"""eval_device_ms: device time of one evaluation pass: the device busy
inside the program's ``fl.evaluate`` spans of the traced trial (the pass
``History.eval_s`` times, which ends in a sync) over their count. Set
against ``eval_ms``, it says how much of a pass is dispatch. No such span
in the trace: no reading."""

from bench import stages

SPAN = "fl.evaluate"


def read(run):
    red = stages.of(run)
    if red is None or SPAN not in red["spans"]:
        return None
    count, _, _, busy = red["spans"][SPAN]
    return 1e3 * busy / count
