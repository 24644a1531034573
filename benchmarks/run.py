"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per the repo convention).

  PYTHONPATH=src python -m benchmarks.run            # fast scale (CPU)
  PYTHONPATH=src python -m benchmarks.run --full     # paper scale
  PYTHONPATH=src python -m benchmarks.run --only table1,fig4
  PYTHONPATH=src python benchmarks/run.py ...        # script form works too
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/run.py` script execution:
    # put the repo root on sys.path so `from benchmarks import ...` resolves
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale m/rounds/trials (slow)")
    ap.add_argument("--only", default="all")
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.configure()
    from benchmarks import (common, fig4_silhouette, fig5_comm_efficiency,
                            fig6_parallel_ucfl, fig7_minibatch, kernel_bench,
                            participation_sweep, roofline_report,
                            round_engine, table1_accuracy, table2_worst_user)

    class _Suite:
        """Adapter exposing a bare row function as a suite module."""

        def __init__(self, fn):
            self.run = fn

    scale = common.FULL if args.full else common.FAST
    suites = {
        "kernel": kernel_bench,
        "roofline": roofline_report,
        # also emits BENCH_round_engine.json (steady-state round walltime,
        # dense vs cohort vs padded-availability) at the repo root
        "round_engine": round_engine,
        "table1": table1_accuracy,
        "table2": table2_worst_user,
        "fig4": fig4_silhouette,
        "fig5": fig5_comm_efficiency,
        "fig6": fig6_parallel_ucfl,
        "fig7": fig7_minibatch,
        "participation": participation_sweep,
        # two-tier topology replay + Pareto selection sweep; its own
        # suite (not inside participation.run) so `all` runs each once
        "hier": _Suite(participation_sweep.run_hier),
    }
    only = None if args.only == "all" else set(args.only.split(","))
    print("name,us_per_call,derived")
    t0 = time.time()
    all_rows = []
    for name, mod in suites.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", file=sys.stderr, flush=True)
        all_rows.extend(mod.run(scale))
    print(f"# total {len(all_rows)} rows in {time.time() - t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
