"""trial_s: the window's seconds over the whole trials it completed.

A trial is one training run: the Eq. 9 init, the warm-up round, the
timed rounds and the evaluation passes."""


def read(run):
    return run["window_s"] / len(run["trials"])
