"""The femnist-ucfl cell on the CPU at a small size that keeps its
distinctive shapes: a client count that is not a multiple of 8, 62
classes, writers of 204 and 23 samples, a cohort that is a small share
of the writers, and an Eq. 9 init that chunks the client axis with a
padded last chunk. A whole run of the harness: the sound program comes
out correct under the cell's own limits, and a program whose rounds leave
the state unchanged or write a mixed model into a second writer's row
does not. Then the cell's two trace readers on a hand-made trace."""
import dataclasses
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec, stages  # noqa: E402

SEED = 2**41 + 17
PEAK = spec.load_json(ROOT / "bench" / "peaks.json")["devices"]["TPU v5e"]


@pytest.fixture(scope="module")
def cell():
    cell = spec.load_cell("femnist-ucfl", ROOT)
    # 13 writers: not a multiple of 8, and chunks of 5, 5 and a padded 3
    conf = dict(cell.config, clients=13, chunk_size=5)
    part = dict(cell.traffic["participation"], cohort_size=4)
    traf = dict(cell.traffic, participation=part, rounds=2, eval_every=2)
    return dataclasses.replace(cell, config=conf, traffic=traf)


def test_cell_keeps_femnist_shapes(cell):
    from repro.core import ucfl

    conf = cell.config
    assert conf["num_classes"] == 62 and conf["image_hw"] == [28, 28]
    assert (conf["train_per_client"], conf["test_per_client"]) == (204, 23)
    assert conf["clients"] % 8
    assert ucfl.init_chunk(conf["clients"], conf["train_per_client"], 50,
                           conf["chunk_size"]) == 5


def _run(cell, tmp_path, hook=None):
    return harness.run_cell(cell, SEED, 0.0, False,
                            t_start=time.perf_counter(), peak=PEAK,
                            trace_dir=tmp_path, log=lambda *a: None,
                            strategy_hook=hook)


def _unchanged(strategy):
    """Every round returns the state it was given."""
    def round(state, data, key, cohort=None):
        return state, {"cohort_size": 0}
    return dataclasses.replace(strategy, round=round)


def _misrouted(strategy):
    """Each round writes one member's new model into another's row too."""
    inner = strategy.round

    def round(state, data, key, cohort=None):
        state, metrics = inner(state, data, key, cohort)
        members = cohort.indices[cohort.mask]
        slab = state["params"]
        return dict(state, params=slab.at[members[0]].set(
            slab[members[1]])), metrics
    return dataclasses.replace(strategy, round=round)


def test_sound_program_is_correct(cell, tmp_path):
    res = _run(cell, tmp_path)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(res["checks"]) == set(cell.limits)
    assert res["compiles"]["window_programs"] == 0


@pytest.mark.parametrize("hook", [_unchanged, _misrouted],
                         ids=["unchanged", "misrouted"])
def test_broken_round_is_not_correct(cell, tmp_path, hook):
    res = _run(cell, tmp_path, hook)
    assert res["correct"] is False
    assert [n for n, c in res["checks"].items()
            if not c["value"] <= c["limit"]]


# times in ns; the trial span is [0, 1000]. The init's three programs run
# in [100, 400]: gradients 100-250 (two ops, one inside the other), Gram
# 260-300 (the padding copy and the kernel), W 310-320; a round and an
# evaluation follow.
HAND = [
    ["span", -1, "bench.trial", 0, 1000],
    ["span", -1, "fl.init", 50, 400],
    ["span", -1, "fl.init.gradients", 60, 90],
    ["span", -1, "fl.init.gram", 90, 95],
    ["span", -1, "fl.init.weights", 95, 98],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 60, 61],
    ["module", 0, "jit__special_round_uploads(1)", 100, 250],
    ["op", 0, "while.1", 100, 250, "fl.init.gradients"],
    ["op", 0, "fusion.1", 120, 200, "fl.init.gradients"],
    ["module", 0, "jit_gram_pallas(2)", 260, 300],
    ["op", 0, "fusion.2", 260, 270, "fl.init.gram"],
    ["op", 0, "gram_pallas.1", 270, 300, "fl.init.gram"],
    ["module", 0, "jit__delta_and_weights(3)", 310, 320],
    ["op", 0, "fusion.3", 310, 320, "fl.init.weights"],
    ["module", 0, "jit__masked(4)", 500, 600],
    ["op", 0, "while.4", 500, 580, "fl.local_sgd"],
    ["op", 0, "custom-call.4", 580, 600, "fl.mix"],
    ["op", 0, "dot", 700, 720, ""],
]


def _trace_run(cell, monkeypatch, events):
    red = stages.reduce(events)
    monkeypatch.setattr(stages, "of", lambda _: red)
    return {"trace": red, "config": spec.load_cell("femnist-ucfl",
                                                   ROOT).config,
            "traffic": cell.traffic, "peak": PEAK}


def test_init_device_ms_reads_the_init_stages(cell, monkeypatch):
    run = _trace_run(cell, monkeypatch, HAND)
    # 150 + 40 + 10 ns of stages over one fl.init span
    assert spec.reader("init_device_ms", ROOT)(run) == pytest.approx(200e-6)
    no_stages = [e[:5] + [""] if e[0] == "op" else e for e in HAND]
    run = _trace_run(cell, monkeypatch, no_stages)
    assert spec.reader("init_device_ms", ROOT)(run) is None


def test_gram_tiled_roofline_reads_the_gram_program(cell, monkeypatch):
    from bench.costs import gram

    run = _trace_run(cell, monkeypatch, HAND)
    m, d = 3550, 48846
    bound = max(gram.flops(m, d) / PEAK["bf16_flops_per_s"],
                gram.hbm_bytes(m, d) / PEAK["hbm_bytes_per_s"])
    value = spec.reader("gram_tiled_roofline", ROOT)(run)
    assert value == pytest.approx(100.0 * bound / 40e-9)
    without = [e for e in HAND if not (e[0] == "module"
                                       and e[2].startswith("jit_gram"))]
    run = _trace_run(cell, monkeypatch, without)
    assert spec.reader("gram_tiled_roofline", ROOT)(run) is None
