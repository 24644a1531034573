"""peak_hbm_gb: peak bytes in use on the fullest chip after set-up: the
inputs made and one whole trial of the cell's programs (init, rounds,
evaluation), read at that fixed point of every run."""


def read(run):
    return run["setup_peak_bytes"] / 1e9
