"""Benchmark one cell of BENCHMARK.json on the chip this process finds.

    python3 bench/run.py --workload paper-ucfl --seed 7 --seconds 10 --trace 0

Prints progress and the compared numbers with their limits on standard
error, and as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
``compiles`` (programs compiled or loaded in set-up and in the window, and
the set-up's persistent-cache misses: a run that compiled cold has
misses), with a trace ``breakdown``, and last ``checks``. Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for, when
the chip has no row in ``bench/peaks.json``, or when the program cannot
be imported. JAX's persistent compilation cache is kept in
``bench/.jax_cache`` of the checkout, so only a cell's first run there
compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / "bench" / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from bench import spec

    try:
        cell = spec.load_cell(args.workload)
    except KeyError:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    peaks = spec.load_json(ROOT / "bench" / "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        return 4

    from bench import harness

    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        peak=peaks[kind], trace_dir=TRACE_DIR / args.workload)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
