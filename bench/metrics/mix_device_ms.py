"""mix_device_ms: device time of the ``fl.mix`` stage (the rows from W,
the mix-scatter kernel and its padding copy, or the serve) in one
execution of the jitted cohort round: the union of the stage's op
intervals in the traced trial over the round program's executions, as
``round_device_ms`` counts them. No such stage in the trace: no
reading."""

from bench import stages
from bench.trace import matching

ROUND_MODULE = r"^jit__masked(\(|$|\.)"
STAGE = "fl.mix"


def read(run):
    red = stages.of(run)
    if red is None or not red["stages"].get(STAGE):
        return None
    count, _ = matching(red["modules"], ROUND_MODULE)
    return 1e3 * red["stages"][STAGE] / count if count else None
