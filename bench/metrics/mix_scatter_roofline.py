"""mix_scatter_roofline: the slab mix-scatter kernel's time against its
roofline, in percent: max(bytes / HBM bandwidth, operations / bf16 peak)
over the kernel's device time per call. Bytes and operations from shapes
(bench/costs/mix_scatter.py) at m clients, c cohort slots and the
state's aligned width."""

from bench.costs import mix_scatter
from bench.trace import matching

KERNEL_OP = r"^masked_mix_scatter_pallas"


def read(run):
    red = run["trace"]
    if red is None:
        return None
    count, seconds = matching(red["ops"], KERNEL_OP)
    if not count:
        return None
    config, peak = run["config"], run["peak"]
    m, d = config["clients"], config["params_aligned"]
    c = run["traffic"]["participation"]["cohort_size"]
    bound = max(mix_scatter.hbm_bytes(m, c, d) / peak["hbm_bytes_per_s"],
                mix_scatter.flops(m, c, d) / peak["bf16_flops_per_s"])
    return 100.0 * bound / (seconds / count)
