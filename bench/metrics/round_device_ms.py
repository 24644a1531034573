"""round_device_ms: device time of one execution of the jitted cohort
round (ucfl's ``_masked`` program: gather, local SGD, mix-scatter), the
union of its ops' intervals, averaged over the executions traced (the
warm-up round and every timed round)."""

from bench.trace import matching

ROUND_MODULE = r"^jit__masked(\(|$|\.)"


def read(run):
    red = run["trace"]
    if red is None:
        return None
    count, seconds = matching(red["modules"], ROUND_MODULE)
    return 1e3 * seconds / count if count else None
