"""round_mfu: LeNet training operations of the window's rounds over their
time and the chip's bf16 peak, in percent.

Operations from shapes (bench/costs/lenet.py): cohort slots x the
samples of the whole batches of an epoch x epochs x operations per
sample, for every round. The time is the untraced window's sum of
``History.wall_s``: all the time of the rounds loop, its cohort draws,
dispatch and finite checks, without the evaluation passes, whose
operations are not counted."""

from bench.costs import lenet


def read(run):
    config, trials = run["config"], run["trials"]
    train, _ = lenet.config_flops(config)
    b = config["batch_size"]
    samples = (config["train_per_client"] // b) * b * config["local_epochs"]
    cohort = run["traffic"]["participation"]["cohort_size"]
    per_round = cohort * samples * train
    flops = per_round * sum(t["rounds"] for t in trials)
    seconds = sum(t["wall_s"] for t in trials)
    return 100.0 * flops / seconds / run["peak"]["bf16_flops_per_s"]
