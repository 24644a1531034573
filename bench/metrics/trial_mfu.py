"""trial_mfu: the operations a ucfl trial needs over its time and the
chip's bf16 peak, in percent. It bounds gram_roofline from above on the
same end-to-end metric, trial_s: a change that takes the Gram kernel off
the path leaves that roofline silent, and this share still reads.

Operations from shapes: the special round's gradients (every client's
whole Eq. 10 minibatches), the Eq. 9 Gram (2 m^2 d), the timed rounds'
local SGD and the evaluation passes' forward passes. The warm-up round,
whose result is discarded, does not count. The time is the untraced
window's sum of trial host times."""

from bench.costs import gram, lenet


def read(run):
    config, traffic, trials = run["config"], run["traffic"], run["trials"]
    train, forward = lenet.config_flops(config)
    m, n = config["clients"], config["train_per_client"]
    vb = traffic["strategy_args"]["var_batch_size"]
    b = config["batch_size"]
    cohort = traffic["participation"]["cohort_size"]
    init = m * (n // vb) * vb * train + gram.flops(m, config["params"])
    per_round = cohort * (n // b) * b * config["local_epochs"] * train
    per_eval = m * config["test_per_client"] * forward
    flops = sum(init + t["rounds"] * per_round + t["evals"] * per_eval
                for t in trials)
    seconds = sum(t["host_s"] for t in trials)
    return 100.0 * flops / seconds / run["peak"]["bf16_flops_per_s"]
