"""device_idle_share: 100 x (1 - busy / window) over the traced trial.

Busy is the union of the intervals in which an operation ran on a chip
(averaged over the chips used); the window is the trial's host span."""


def read(run):
    red = run["trace"]
    if red is None or red["chips"] == 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
