"""The trace reducer: on a hand-made trace whose numbers are worked out
below, and on a trace of one paper-ucfl trial recorded on a TPU v5e."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = pathlib.Path(__file__).with_name("data") / "paper_trial.json.gz"

# times in ns; the trial span is [0, 1000]
HAND = [
    ["span", -1, "bench.trial", 0, 1000],
    ["span", -1, "bench.init", 0, 300],
    ["span", -1, "bench.round", 400, 540],
    ["span", -1, "bench.round", 600, 700],
    ["module", 0, "jit_init(1)", 100, 250],
    ["op", 0, "gram", 100, 150],
    ["op", 0, "fusion.1", 140, 200],  # overlaps the gram op
    ["module", 0, "jit__masked(2)", 420, 560],
    ["op", 0, "fusion.2", 420, 520],
    ["op", 0, "custom-call", 530, 550],
    ["module", 0, "jit__masked(2)", 620, 700],
    ["op", 0, "fusion.2", 620, 690],
    ["op", 0, "late", 990, 1100],  # clipped to the window
]


def test_hand_made_trace():
    red = trace.reduce(HAND)
    # busy: [100, 200] + [420, 520] + [530, 550] + [620, 690] + [990, 1000]
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["chips"] == 1
    assert red["ops"]["fusion.2"] == [2, pytest.approx(170e-9)]
    assert red["ops"]["late"] == [1, pytest.approx(10e-9)]
    assert red["modules"]["jit__masked(2)"] == [2, pytest.approx(190e-9)]
    assert trace.matching(red["modules"], r"^jit__masked") == (
        2, pytest.approx(190e-9))
    # idle gaps: [0,100] init, [200,420] mid 310 -> trial, [520,530] round,
    # [550,620] mid 585 -> trial, [690,990] mid 840 -> trial
    assert red["idle_by_span"] == {
        "bench.init": pytest.approx(100e-9),
        "bench.round": pytest.approx(10e-9),
        "bench.trial": pytest.approx(590e-9)}
    assert red["idle_gaps"] == 5
    assert red["longest_gap_s"] == pytest.approx(300e-9)
    bd = trace.breakdown(red, top=2)
    assert [n for n, _ in bd["device_ops"]] == ["fusion.2", "fusion.1"]
    assert bd["idle_gaps"][0][0] == "bench.trial"


def test_no_trial_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([e for e in HAND if e[2] != "bench.trial"])


def _sweep_busy(ops, t0, t1):
    """Busy time by a sweep over start and end points: an independent
    count of the union the reducer forms."""
    points = sorted([(max(s, t0), 1) for _, _, _, s, e in ops if e > t0
                     and s < t1] + [(min(e, t1), -1) for _, _, _, s, e in ops
                                    if e > t0 and s < t1])
    depth, busy, last = 0, 0.0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    events = trace.load(RECORDED)
    red = trace.reduce(events)
    trial = [e for e in events if e[2] == "bench.trial"][0]
    ops = [e for e in events if e[0] == "op" and e[1] == 0]
    assert red["busy_s"] == pytest.approx(
        _sweep_busy(ops, trial[3], trial[4]) / 1e9, rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(red["idle_by_span"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    count, seconds = trace.matching(red["modules"], r"^jit__masked")
    assert count > 0 and 0 < seconds < red["busy_s"]
    total_ops = sum(c[1] for c in red["ops"].values())
    assert total_ops >= red["busy_s"] * (1 - 1e-9)
    assert np.isfinite(list(red["idle_by_span"].values())).all()


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
@pytest.mark.parametrize("metric", ["gram_roofline", "mix_scatter_roofline",
                                    "round_device_ms", "device_idle_share"])
def test_trace_readers_on_the_recorded_trial(metric):
    """Each trace reader finds its program or kernel in the recorded
    paper-ucfl trial, and a share of a roofline stays within 100 %."""
    from bench import spec

    cell = spec.load_cell("paper-ucfl", ROOT)
    run = {"trace": trace.reduce(trace.load(RECORDED)),
           "config": cell.config, "traffic": cell.traffic,
           "peak": spec.load_json(ROOT / "bench" / "peaks.json")[
               "devices"]["TPU v5e"]}
    value = spec.reader(metric, ROOT)(run)
    assert value is not None and 0 < value
    if metric.endswith("_roofline") or metric.endswith("_share"):
        assert value < 100
