"""The control of the check: the float32 reference put in the program's
place but computed in bfloat16 (the precision a later change would be
tempted to drop to) has to come out not correct under the cell's limits.
On the CPU at a small size; the readings at the cells' own sizes on the
chip come from bench/calibrate.py and are in PERF.md."""
import dataclasses
import pathlib
import sys

import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import datagen, harness, spec  # noqa: E402


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.load_benchmark(ROOT)["workloads"]])
def test_bfloat16_control_is_not_correct(workload):
    cell = spec.load_cell(workload, ROOT)
    conf = dict(cell.config, clients=6, train_per_client=100,
                test_per_client=20, chunk_size=None)
    traf = dict(cell.traffic, participation=dict(
        cell.traffic["participation"], cohort_size=3))
    cell = dataclasses.replace(cell, config=conf, traffic=traf)
    seed = 2**33 + 5
    arrays, params0 = datagen.generate(conf, seed)
    key, pseed = datagen.trial_seeds(seed, 0)
    correct, checks, _ = harness.compare(cell, None, arrays, params0, key,
                                         pseed, dtype=jnp.bfloat16)
    assert correct is False, checks
