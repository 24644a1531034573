"""gram_roofline: the Eq. 9 Gram program's time against its roofline, in
percent: max(operations / bf16 peak, bytes / HBM bandwidth) over the
device time of one execution of ``jit_gram_pallas``. Operations and
bytes from shapes (bench/costs/gram.py) at m clients and the model's
true width: the (m, d) gradients read once, the (m, m) Gram written.

The time is the program's, not the kernel op's alone: the program pads
the gradients to the kernel's tiles first, and at paper scale XLA keeps
that padded copy in on-chip memory, where the kernel op reads it faster
than HBM could deliver it. The program's time covers the one read from
HBM. No such program in the trace (the Gram fused into a larger one):
no reading."""

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
