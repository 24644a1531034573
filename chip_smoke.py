"""Run the user-centric cohort round on a TPU, end to end, and check it.

    python chip_smoke.py              # phases A-C on one chip
    python chip_smoke.py --chips 4    # Phase A sharded and replicated over
                                      # four chips, each against one chip

Every phase drives the normal entry points: ``simulation.run`` over the
registered ``ucfl`` strategy at paper scale (``benchmarks.common.FULL``:
m=20 clients, n=500 samples of 28x28, 20 classes, LeNet), or the kernel
dispatch layer ``repro.kernels.ops``.

  A  full personalization (Eq. 8 mix over the Eq. 9 W): ``ucfl`` under
     label shift, cohorts of 10, 3 rounds with eval;
  B  clustered (§IV-B): ``ucfl_k2`` under covariate shift, same scale;
  C  the large-m row path: cohort gather and mix-scatter at m=16,384,
     c=64, d=45,312 (a 3 GB state) through the HBM-resident variant, and
     the VMEM-slab kernel just inside ``slab_fits``.

A and B repeat the run with ``kernel_impl="ref"`` from the same seed and
C compares against ``repro.kernels.ref``. Each phase prints one JSON line
(compile seconds, steady round or call seconds, accuracy, largest
difference from the reference, peak device bytes). The last line is
``{"ok": true, "device": {...}}``. The script exits non-zero, printing no
such line, when the device is not a TPU, when ``REPRO_KERNEL_IMPL``
names anything but a ``pallas*`` path, when the compiled Phase A round
holds no Pallas kernel, or when any phase raises or misses its tolerance.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.federated import simulation  # noqa: E402
from repro.federated.participation import ParticipationConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.masked_mix_scatter import slab_fits  # noqa: E402
from repro.models import lenet  # noqa: E402

ROUNDS = 3
COHORT = 10
ROWS_SHAPE = (16384, 64, 45312)  # Phase C: (m, c, d)

# Tolerances against the reference run. The chip computes f32 matmuls at
# its default precision, which rounds operands to bf16 (relative error
# 2^-9), and the Pallas mix kernel and XLA's einsum round at different
# points; 3 rounds of 10 momentum-SGD steps carry that difference
# forward. So results agree to a few bf16 ulps of the parameters, not
# bit for bit, and a few test predictions may flip.
PARAMS_TOL = 5e-2  # largest |param - ref param| after the run
ACC_TOL = 0.05  # |accuracy - ref accuracy|, average and worst user
# Phase C mixes N(0, 1) rows with a row-stochastic W, so |mixed| < 5 and
# one bf16 rounding of the operands moves a product by at most ~1e-2.
ROWS_TOL = 2e-2


@contextlib.contextmanager
def compile_seconds():
    """Sum of JAX's trace, lowering and XLA-compile durations inside."""
    box = [0.0]

    def listen(event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            box[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@jax.jit
def max_abs_diff(a, b):
    return jnp.max(jnp.abs(a - b))


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_phase(scenario, strategy, scale, *, impl, rounds=ROUNDS,
              cohort=COHORT, mesh=None, shard_state=False, seed=0):
    """One ``simulation.run`` of a registered strategy from ``seed``."""
    dkey, mkey, skey = jax.random.split(jax.random.PRNGKey(seed), 3)
    data = common.scenario_data(scenario, dkey, scale)
    params0 = common.make_params0(
        mkey, scale, common.num_classes_for(scenario, scale))
    strat = common.make_strategy(strategy, params0, scale, mesh=mesh,
                                 shard_state=shard_state, kernel_impl=impl)
    with compile_seconds() as comp:
        hist = simulation.run(
            strat, lenet.apply, data, skey, rounds=rounds,
            participation=ParticipationConfig(cohort_size=cohort, seed=seed))
    return {"compile_s": comp[0], "round_s": hist.wall_s / rounds,
            "avg_acc": hist.final_avg, "worst_acc": hist.final_worst,
            "params": np.asarray(hist.state["params"]),
            "round_fn": strat.round.masked_jit.__name__}


def compare(got, want):
    """Largest parameter and accuracy gaps of two ``run_phase`` results."""
    return {"max_abs_diff": float(np.max(np.abs(got["params"]
                                                 - want["params"]))),
            "avg_acc_ref": want["avg_acc"],
            "worst_acc_ref": want["worst_acc"]}


def check_close(label, diff, got):
    gaps = (diff["max_abs_diff"], abs(got["avg_acc"] - diff["avg_acc_ref"]),
            abs(got["worst_acc"] - diff["worst_acc_ref"]))
    if not (gaps[0] <= PARAMS_TOL and max(gaps[1:]) <= ACC_TOL):
        raise AssertionError(
            f"{label}: params gap {gaps[0]} (tol {PARAMS_TOL}), accuracy "
            f"gaps {gaps[1:]} (tol {ACC_TOL})")


def phase_line(label, res, **extra):
    return json.dumps({
        "phase": label, "compile_s": res["compile_s"],
        "round_s": res["round_s"], "avg_acc": res["avg_acc"],
        "worst_acc": res["worst_acc"], **extra,
        "peak_bytes_in_use": peak_bytes()})


def phase_rows(m, c, d, *, impl, seed=0):
    """Cohort gather + mix-scatter through ``ops`` at (m, c, d) vs ref.

    The last slot is a pad (sentinel index m, mask 0). Returns compile
    and steady call seconds and the largest difference from ref; the
    gather is a pure row copy, so it must match exactly."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    full = jax.random.normal(k[0], (m, d), jnp.float32)
    idx = jnp.sort(jax.random.choice(k[1], m, (c,), replace=False))
    idx = idx.astype(jnp.int32).at[-1].set(m)
    mask = jnp.arange(c) < c - 1
    w = jax.random.uniform(k[2], (c, c)) * mask[None, :]
    w = w / jnp.sum(w, axis=1, keepdims=True)
    theta = jax.random.normal(k[3], (c, d), jnp.float32)

    with compile_seconds() as comp:
        rows = jax.block_until_ready(ops.cohort_gather(full, idx, impl=impl))
        gather_diff = float(max_abs_diff(rows, ref.cohort_gather(full, idx)))
        del rows
        want = jax.jit(ref.masked_mix_scatter, donate_argnums=4)(
            w, theta, idx, mask, full.copy())
        out = ops.masked_mix_scatter(w, theta, idx, mask, full, impl=impl)
        scatter_diff = float(max_abs_diff(out, want))
        del want
    t0 = time.perf_counter()
    jax.block_until_ready(ops.cohort_gather(out, idx, impl=impl))
    gather_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        ops.masked_mix_scatter(w, theta, idx, mask, out, impl=impl))
    scatter_s = time.perf_counter() - t0
    if gather_diff != 0.0 or not scatter_diff <= ROWS_TOL:
        raise AssertionError(
            f"rows m={m} c={c} impl={impl}: gather diff {gather_diff} "
            f"(must be 0), mix-scatter diff {scatter_diff} (tol {ROWS_TOL})")
    return {"compile_s": comp[0], "gather_s": gather_s,
            "mix_scatter_s": scatter_s, "gather_max_abs_diff": gather_diff,
            "mix_scatter_max_abs_diff": scatter_diff}


def slab_bound(c):
    """Largest 8-row multiple m that ``slab_fits`` still accepts."""
    m = 8
    while slab_fits(m + 8, c):
        m += 8
    return m


def round_kernels(ir_dir, round_fn):
    """Pallas kernels in the dumped StableHLO of the jitted round."""
    return sum(p.read_text().count("tpu_custom_call")
               for p in pathlib.Path(ir_dir).glob(f"*jit_{round_fn}_*"))


def one_chip(impl):
    scale = common.FULL
    with tempfile.TemporaryDirectory() as ir_dir:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            a = run_phase("label_shift", "ucfl", scale, impl=impl)
        finally:
            jax.config.update("jax_dump_ir_to", "")
        kernels = round_kernels(ir_dir, a["round_fn"])
    if not kernels:
        raise AssertionError(
            "the compiled Phase A round holds no tpu_custom_call: no Pallas "
            "kernel ran on the chip")
    diff = compare(a, run_phase("label_shift", "ucfl", scale, impl="ref"))
    check_close("A", diff, a)
    print(phase_line("A ucfl label_shift", a, round_kernels=kernels, **diff),
          flush=True)

    b = run_phase("covariate_label_shift", "ucfl_k2", scale, impl=impl)
    diff = compare(b, run_phase("covariate_label_shift", "ucfl_k2", scale,
                                impl="ref"))
    check_close("B", diff, b)
    print(phase_line("B ucfl_k2 covariate_label_shift", b, **diff),
          flush=True)

    base = impl.removesuffix("_hbm").removesuffix("_slab")
    m, c, d = ROWS_SHAPE
    for variant, rows_m in (("hbm", m), ("slab", slab_bound(c))):
        res = phase_rows(rows_m, c, d, impl=f"{base}_{variant}")
        print(json.dumps({"phase": f"C {variant} m={rows_m} c={c} d={d}",
                          **res, "peak_bytes_in_use": peak_bytes()}),
              flush=True)


def four_chips(impl):
    scale = common.FULL
    one = run_phase("label_shift", "ucfl", scale, impl=impl)
    print(phase_line("A ucfl label_shift 1 device", one), flush=True)
    for shard_state in (True, False):
        res = run_phase("label_shift", "ucfl", scale, impl=impl, mesh=4,
                        shard_state=shard_state)
        label = "A mesh=4 " + ("shard_state" if shard_state else "replicated")
        diff = compare(res, one)
        check_close(label, diff, res)
        print(phase_line(label, res, **diff), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    compile_cache.configure()
    impl = os.environ.get("REPRO_KERNEL_IMPL") or "pallas"
    if not impl.startswith("pallas"):
        print(f"chip_smoke: REPRO_KERNEL_IMPL={impl!r} bypasses the Pallas "
              "kernels; unset it or name a pallas* path", file=sys.stderr)
        return 2
    dev = jax.devices()
    if dev[0].platform != "tpu" or len(dev) != args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), found "
              f"{len(dev)} {dev[0].platform!r}", file=sys.stderr)
        return 2
    (four_chips if args.chips == 4 else one_chip)(impl)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
