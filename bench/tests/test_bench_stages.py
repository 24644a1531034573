"""The reduction of the program's own spans and stages (bench/stages.py): on
a hand-made trace whose numbers are worked out below, on the five-field
paper-ucfl trial recorded before the program had spans, and on a paper-ucfl
trial recorded on a TPU v5e with them."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec, stages, trace  # noqa: E402

DATA = pathlib.Path(__file__).with_name("data")
OLD = DATA / "paper_trial.json.gz"
RECORDED = DATA / "paper_trial_spans.json.gz"
NEW_READERS = ("local_sgd_device_ms", "mix_device_ms", "eval_device_ms",
               "eval_programs", "init_programs")
ROUND_MODULE = r"^jit__masked(\(|$|\.)"

# times in ns; the trial span is [0, 1000]
HAND = [
    ["span", -1, "bench.trial", 0, 1000],
    ["span", -1, "fl.init", 0, 300],
    ["span", -1, "bench.init", 5, 295],
    ["span", -1, "fl.init.gradients", 10, 200],
    ["span", -1, "fl.round", 400, 700],
    ["span", -1, "fl.dispatch", 410, 540],
    ["span", -1, "bench.round", 420, 530],
    ["span", -1, "fl.evaluate", 600, 700],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 20, 25],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 50, 55],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 150, 155],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 250, 255],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 415, 418],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 610, 612],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 620, 622],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 650, 652],
    ["launch", -1, "PJRT_LoadedExecutable_Execute", 900, 905],
    ["module", 0, "jit_init(1)", 100, 250],
    ["op", 0, "gram", 100, 150, ""],
    ["op", 0, "fusion.1", 140, 200, ""],
    ["module", 0, "jit__masked(2)", 420, 560],
    ["op", 0, "fusion.2", 420, 430, "fl.gather"],
    # a while op spans its body's ops: the stage is 90, not 90 + 30 + 30
    ["op", 0, "while.3", 430, 520, "fl.local_sgd"],
    ["op", 0, "fusion.4", 440, 470, "fl.local_sgd"],
    ["op", 0, "fusion.5", 480, 510, "fl.local_sgd"],
    ["op", 0, "custom-call", 530, 550, "fl.mix"],
    ["op", 0, "copy.9", 550, 560, ""],
    ["module", 0, "jit_eval(3)", 610, 690],
    ["op", 0, "dot", 615, 640, ""],
    ["op", 0, "argmax", 660, 680, ""],
    ["op", 0, "late", 990, 1100, ""],  # clipped to the window
]


def test_hand_made_trace():
    red = stages.reduce(HAND)
    assert {k: v for k, v in red.items() if k in trace.reduce(
        stages.harness_view(HAND))} == trace.reduce(stages.harness_view(HAND))
    assert red["stages"] == {"fl.gather": pytest.approx(10e-9),
                             "fl.local_sgd": pytest.approx(90e-9),
                             "fl.mix": pytest.approx(20e-9)}
    # [count, total, self (outside nested fl. spans), device busy inside]
    want = {"fl.init": [1, 300, 110, 100],
            "fl.init.gradients": [1, 190, 190, 100],
            "fl.round": [1, 300, 70, 175],
            "fl.dispatch": [1, 130, 130, 110],
            "fl.evaluate": [1, 100, 100, 45]}
    assert red["spans"] == {
        name: [c] + [pytest.approx(x * 1e-9) for x in rest]
        for name, (c, *rest) in want.items()}
    assert red["programs_by_span"] == {
        "fl.init": 4, "fl.init.gradients": 3, "fl.round": 4,
        "fl.dispatch": 1, "fl.evaluate": 3}
    # gaps: [0,100] mid 50 -> fl.init.gradients (innermost of fl.init,
    # bench.init and it); [200,420] -> trial; [520,530] -> bench.round;
    # [560,615] -> fl.round; [640,660] -> fl.evaluate; [680,990] -> trial
    assert red["idle_by_stage"] == {
        "fl.init.gradients": pytest.approx(100e-9),
        "bench.trial": pytest.approx(530e-9),
        "bench.round": pytest.approx(10e-9),
        "fl.round": pytest.approx(55e-9),
        "fl.evaluate": pytest.approx(20e-9)}
    assert red["idle_by_span"] == {"bench.init": pytest.approx(100e-9),
                                   "bench.round": pytest.approx(10e-9),
                                   "bench.trial": pytest.approx(605e-9)}


def test_harness_view_is_what_the_harness_reads():
    view = stages.harness_view(HAND)
    assert all(len(e) == 5 for e in view)
    assert {e[0] for e in view} == {"op", "module", "span"}
    assert {e[2] for e in view if e[0] == "span"} == {
        "bench.trial", "bench.init", "bench.round"}


def _run(red, cell):
    return {"trace": red, "config": cell.config, "traffic": cell.traffic,
            "peak": spec.load_json(ROOT / "bench" / "peaks.json")[
                "devices"]["TPU v5e"]}


def _reading(metric, run, red, monkeypatch):
    monkeypatch.setattr(stages, "of", lambda _: red)
    return spec.reader(metric, ROOT)(run)


@pytest.mark.skipif(not OLD.exists(), reason="no recorded trace")
def test_old_trace_reduces_and_keeps_the_four_readings(monkeypatch):
    """The five-field trace recorded before the program had spans: the
    reduction keeps the harness's keys and values, has no stages or spans,
    the four trace readers read what they read before, and the five new
    readers read nothing."""
    cell = spec.load_cell("paper-ucfl", ROOT)
    events = trace.load(OLD)
    red = stages.reduce(events)
    base = trace.reduce(events)
    assert {k: red[k] for k in base} == base
    assert red["stages"] == red["spans"] == red["programs_by_span"] == {}
    assert red["idle_by_stage"] == base["idle_by_span"]
    run = _run(base, cell)
    before = {"device_idle_share": 65.2018320713255,
              "round_device_ms": 4.112953803278688,
              "gram_roofline": 42.07815220654022,
              "mix_scatter_roofline": 52.58278046864298}
    for metric, value in before.items():
        assert spec.reader(metric, ROOT)(run) == pytest.approx(value,
                                                               rel=1e-12)
    for metric in NEW_READERS:
        assert _reading(metric, run, red, monkeypatch) is None


@pytest.fixture(scope="module")
def recorded():
    events = trace.load(RECORDED)
    return events, stages.reduce(events)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trial_keeps_the_harness_reduction(recorded):
    events, red = recorded
    base = trace.reduce(stages.harness_view(events))
    assert {k: red[k] for k in base} == base
    assert sum(red["idle_by_stage"].values()) == pytest.approx(
        sum(base["idle_by_span"].values()), rel=1e-9)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trial_stages_fill_the_round(recorded):
    """The stages are a part of the round program's time, and ops of the
    round with no stage take at most 5 % of it."""
    _, red = recorded
    count, seconds = trace.matching(red["modules"], ROUND_MODULE)
    assert count > 0
    staged = sum(red["stages"].values())
    assert set(red["stages"]) == {"fl.gather", "fl.local_sgd", "fl.mix"}
    assert red["stages"]["fl.local_sgd"] + red["stages"]["fl.mix"] <= seconds
    assert 0 <= seconds - staged <= 0.05 * seconds


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trial_idle_has_a_program_span(recorded):
    """At most 5 % of the idle time falls where only the harness's trial
    span, or none, is open."""
    _, red = recorded
    idle = red["idle_by_stage"]
    bare = idle.get(trace.TRIAL_SPAN, 0.0) + idle.get("outside", 0.0)
    assert bare <= 0.05 * sum(idle.values())


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trial_spans_and_programs(recorded):
    _, red = recorded
    sp, progs = red["spans"], red["programs_by_span"]
    rounds = sp["fl.round"][0]
    assert sp["fl.cohort"][0] == sp["fl.dispatch"][0] == rounds
    assert sp["fl.init"][0] == sp["fl.warmup"][0] == 1
    assert sp["fl.evaluate"][0] == sp["fl.check_finite"][0] >= 1
    # one round program per dispatch
    assert progs["fl.dispatch"] == rounds
    # the init's children hold all its launches
    kids = ("fl.init.gradients", "fl.init.gram", "fl.init.weights",
            "fl.init.slab", "fl.init.kmeans")
    assert sum(progs.get(k, 0) for k in kids) == progs["fl.init"]
    for name, (count, total, own, busy) in sp.items():
        assert 0 <= own <= total * (1 + 1e-9) and 0 <= busy <= total, name


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_on_the_recorded_trial(metric, recorded, monkeypatch):
    _, red = recorded
    cell = spec.load_cell("paper-ucfl", ROOT)
    run = _run(red, cell)
    value = _reading(metric, run, red, monkeypatch)
    round_ms = spec.reader("round_device_ms", ROOT)(run)
    assert value is not None and value > 0
    if metric.endswith("_programs"):
        assert value == int(value)
    if metric in ("local_sgd_device_ms", "mix_device_ms"):
        assert value < round_ms


def test_of_reads_nothing_without_a_traced_trial():
    cell = spec.load_cell("paper-ucfl", ROOT)
    assert stages.of({"trace": None, "config": cell.config,
                      "traffic": cell.traffic}) is None
    other = dict(cell.config, clients=cell.config["clients"] + 1)
    assert stages.of({"trace": {}, "config": other,
                      "traffic": cell.traffic}) is None


def test_of_finds_the_trace_the_harness_left(tmp_path, monkeypatch):
    """``of`` reads the newest ``.xplane.pb`` under ``bench/.trace/<cell>``
    of the checkout, for the cell whose files the run holds."""
    import shutil

    import jax

    cell = spec.load_cell("paper-ucfl", ROOT)
    bench = spec.load_benchmark(ROOT)
    work = {w["name"]: w for w in bench["workloads"]}["paper-ucfl"]
    conf = {c["name"]: c for c in bench["configs"]}[work["config"]]
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in (conf["file"], f"bench/traffic/{work['traffic']}.json"):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(ROOT / rel, tmp_path / rel)
    logdir = tmp_path / "bench" / ".trace" / "paper-ucfl"
    with jax.profiler.trace(str(logdir)):
        with jax.profiler.TraceAnnotation(trace.TRIAL_SPAN):
            with jax.profiler.TraceAnnotation("fl.evaluate"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    red = stages.of({"trace": {}, "config": cell.config,
                     "traffic": cell.traffic})
    assert red is not None and red["spans"]["fl.evaluate"][0] == 1
