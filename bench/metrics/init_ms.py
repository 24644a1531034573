"""init_ms: the part of a trial outside the rounds loop, from the untraced
window: the ucfl init (Eq. 9 gradients, Gram, W), the warm-up round on a
copy of the state and its finite check. Mean over the window's trials of
host time minus ``History.wall_s`` minus ``History.eval_s``."""


def read(run):
    trials = run["trials"]
    return 1e3 * sum(t["host_s"] - t["wall_s"] - t["eval_s"]
                     for t in trials) / len(trials)
