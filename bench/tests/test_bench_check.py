"""A whole run of the harness on the CPU at a small size, past its look for
a chip: the sound program comes out correct, and each fault the cell can
have, planted in the timed path underneath, comes out not correct. The
limits are the paper-ucfl cell's own."""
import dataclasses
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import datagen, harness, spec  # noqa: E402

SEED = 2**40 + 11
PEAK = spec.load_json(ROOT / "bench" / "peaks.json")["devices"]["TPU v5e"]


@pytest.fixture(scope="module")
def cell():
    cell = spec.load_cell("paper-ucfl", ROOT)
    conf = dict(cell.config, clients=6, train_per_client=100,
                test_per_client=20)
    part = dict(cell.traffic["participation"], cohort_size=3)
    traf = dict(cell.traffic, participation=part, rounds=2, eval_every=2)
    return dataclasses.replace(cell, config=conf, traffic=traf)


def _run(cell, tmp_path, hook=None):
    return harness.run_cell(cell, SEED, 0.0, False,
                            t_start=time.perf_counter(), peak=PEAK,
                            trace_dir=tmp_path, log=lambda *a: None,
                            strategy_hook=hook)


def test_sound_program_is_correct(cell, tmp_path):
    res = _run(cell, tmp_path)
    assert res["correct"] is True
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(res["checks"]) == set(cell.limits)
    assert list(res)[-1] == "checks"
    assert res["compiles"]["window_programs"] == 0
    assert res["attempted"] == 1 and res["failed"] == 0


def _unchanged(strategy):
    """Every round returns the state it was given."""
    def round(state, data, key, cohort=None):
        return state, {"cohort_size": 0}
    return dataclasses.replace(strategy, round=round)


def _altered(strategy):
    """Each round writes one member's new model into another's row too."""
    inner = strategy.round

    def round(state, data, key, cohort=None):
        state, metrics = inner(state, data, key, cohort)
        members = cohort.indices[cohort.mask]
        slab = state["params"]
        return dict(state, params=slab.at[members[0]].set(
            slab[members[1]])), metrics
    return dataclasses.replace(strategy, round=round)


def _half_batch_loss(logits, labels):
    half = logits.shape[0] // 2
    logp = jax.nn.log_softmax(logits[:half])
    return -jnp.mean(jnp.take_along_axis(logp, labels[:half, None], axis=1))


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_not_correct(cell, tmp_path, monkeypatch, fault):
    hook = {"unchanged": _unchanged, "altered": _altered}.get(fault)
    if fault == "half_batch":
        from repro.federated import client
        monkeypatch.setattr(client, "cross_entropy", _half_batch_loss)
    res = _run(cell, tmp_path, hook)
    assert res["correct"] is False
    failed = [n for n, c in res["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed


def test_same_seed_same_inputs_and_large_seeds_differ(cell):
    a, pa = datagen.generate(cell.config, SEED)
    b, pb = datagen.generate(cell.config, SEED)
    c, _ = datagen.generate(cell.config, SEED % 2**32)
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(pa["f1_w"],
                                                             pb["f1_w"])
    assert not np.array_equal(a["x"], c["x"])
