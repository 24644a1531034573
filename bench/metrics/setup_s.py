"""setup_s: process start to the first timed trial.

Imports, the inputs made on the device, the strategy built, and the
set-up trial that compiles init, the round, evaluation and the finite
check (or finds them in the persistent cache)."""


def read(run):
    return run["setup_s"]
