"""Readings that set the limits of a cell's correctness check.

    python3 bench/calibrate.py --workload paper-ucfl --seeds 101-112 \
        --control-seeds 101-103

For every seed of ``--seeds`` it runs the program's set-up trial (the
trial the check compares, through the same object and call as a run) and
prints its numbers against the float32 reference: the lower readings.
For every seed of ``--control-seeds`` it also puts the reference in the
program's place, computed as each entry of the cell's check module's
``CONTROLS`` says (for ucfl: in bfloat16, the control, and with each
planted fault: half of every batch left out; a mixed model written to a
second client's row), and prints their numbers: the upper readings. A
state left unchanged reads 1 on ``update_gap`` and ``row_gap`` by their
definition and needs no run. One JSON line per reading; needs the chip,
like run.py.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def seeds(text):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / "bench" / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3

    from bench import datagen, harness, program, spec

    cell = spec.load_cell(args.workload)
    traf = cell.traffic
    checker = spec.checker(cell)
    runs = [("program", {})]
    control = list(checker.CONTROLS.items())
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        arrays, params0 = datagen.generate(cell.config, seed)
        data = program.federated_data(arrays)
        key, pseed = datagen.trial_seeds(seed, 0)
        strategy = program.build(cell, params0)
        hist, _ = program.run_trial(strategy, data, key, pseed, traf,
                                    traf["check_rounds"],
                                    traf["check_rounds"])
        prog = checker.outputs(strategy, hist)
        del hist, strategy
        todo = (runs if seed in args.seeds else []) + (
            control if seed in args.control_seeds else [])
        for name, kw in todo:
            correct, _, values = harness.compare(cell, prog, arrays, params0,
                                                 key, pseed, **kw)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "reading": name, "correct": correct,
                              **values}), flush=True)
        print(f"calibrate: seed {seed} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
