"""round_ms: all the time of the steady rounds over all the rounds.

Sum over the window's trials of ``History.wall_s + History.eval_s`` (the
rounds loop, cohort draws, dispatch, finite checks and the evaluation
passes at the cell's cadence), divided by the rounds they ran."""


def read(run):
    trials = run["trials"]
    rounds = sum(t["rounds"] for t in trials)
    return 1e3 * sum(t["wall_s"] + t["eval_s"] for t in trials) / rounds
