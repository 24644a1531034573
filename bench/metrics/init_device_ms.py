"""init_device_ms: device time of one strategy init, by the program's
device stages: the union of the op intervals of the ``fl.init.`` stages
(ucfl's Eq. 9 gradient program, Gram and W) in the traced trial, over
the count of the program's ``fl.init`` spans. A program without those
stages (ucfl before they were scoped): no reading."""

from bench import stages

PREFIX = "fl.init."
SPAN = "fl.init"


def read(run):
    red = stages.of(run)
    if red is None or SPAN not in red["spans"]:
        return None
    # the init's programs run one after another on a chip, so the union
    # of their stages is their sum
    busy = [s for name, s in red["stages"].items() if name.startswith(PREFIX)]
    if not busy:
        return None
    return 1e3 * sum(busy) / red["spans"][SPAN][0]
