"""gram_tiled_roofline: the tiled Eq. 9 Gram program's time against its
roofline, in percent, at cross-device m: max(operations / bf16 peak,
bytes / HBM bandwidth) over the device time of one execution of
``jit_gram_pallas``. Operations and bytes from shapes (bench/costs/gram.py)
at m clients and the model's true width: every product g_i . g_j once,
the (m, d) gradients read once and the (m, m) Gram written, whatever the
tiling re-reads, so the reading cannot pass 100 %. The time is the
program's: the padding copy to the kernel's tiles and the kernel. No such
program in the trace: no reading."""

from bench.costs import gram
from bench.trace import matching

PROGRAM = r"^jit_gram_pallas(\(|$)"


def read(run):
    red = run["trace"]
    if red is None:
        return None
    count, seconds = matching(red["modules"], PROGRAM)
    if not count:
        return None
    config, peak = run["config"], run["peak"]
    m, d = config["clients"], config["params"]
    bound = max(gram.flops(m, d) / peak["bf16_flops_per_s"],
                gram.hbm_bytes(m, d) / peak["hbm_bytes_per_s"])
    return 100.0 * bound / (seconds / count)
