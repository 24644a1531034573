"""eval_ms: host time of one evaluation pass (``client.evaluate`` over
every client's test set), from the untraced window: the sum of
``History.eval_s`` over the sum of evaluation passes."""


def read(run):
    trials = run["trials"]
    return 1e3 * sum(t["eval_s"] for t in trials) / sum(t["evals"]
                                                         for t in trials)
