"""The program's own tracing: the ``fl.`` host spans that ``simulation.run``
and the ucfl init open, and the ``fl.`` stage scopes of the cohort round
programs and of ucfl's init programs (``repro.federated.spans``), read
back the way the benchmark's trace reducer reads them
(``bench/stages.py``)."""
import functools
import glob
import os
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

from repro.core import FedConfig, REGISTRY
from repro.core.similarity import RefreshConfig
from repro.data import synthetic
from repro.federated import faults as faults_lib
from repro.federated import simulation, spans
from repro.federated.participation import Cohort, ParticipationConfig
from repro.federated.transport import TransportConfig
from repro.models import lenet

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import stages  # noqa: E402

ROUNDS, EVAL_EVERY = 4, 2
INIT_CHILDREN = (spans.INIT_GRADIENTS, spans.INIT_GRAM, spans.INIT_WEIGHTS,
                 spans.INIT_SLAB)
ROUND_CHILDREN = (spans.COHORT, spans.DISPATCH, spans.CHECK_FINITE,
                  spans.EVALUATE)


@functools.lru_cache(maxsize=1)
def _setup():
    key = jax.random.PRNGKey(3)
    dkey, mkey = jax.random.split(key)
    data = synthetic.concept_shift(dkey, m=6, n=40, n_test=10, num_classes=4,
                                   groups=2, hw=(16, 16), channels=1,
                                   noise=1.0)
    params0 = lenet.init(mkey, input_hw=(16, 16), channels=1, num_classes=4)
    return data, params0


def _strategy(name, **fed):
    data, params0 = _setup()
    cfg = FedConfig(lr=0.1, momentum=0.9, epochs=1, batch_size=20, **fed)
    if name == "fedavg":
        return REGISTRY[name](lenet.apply, params0, cfg)
    return REGISTRY[name](lenet.apply, params0, cfg, var_batch_size=20)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The events of a tiny ucfl run under the profiler, and the stages of
    the programs it ran."""
    from jax.profiler import ProfileData

    data, _ = _setup()
    strategy = _strategy("ucfl")
    part = ParticipationConfig(cohort_size=3, seed=1)
    key = jax.random.PRNGKey(0)
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        simulation.run(strategy, lenet.apply, data, key, rounds=ROUNDS,
                       eval_every=EVAL_EVERY, participation=part)
    path = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    return (stages.events_from_profile(ProfileData.from_file(path)),
            stages.op_stages(path))


def _spans(events, name):
    return [(e[3], e[4]) for e in events if e[0] == "span" and e[2] == name]


def _inside(inner, outer):
    return any(s <= a and b <= e for s, e in outer for a, b in [inner])


def test_host_spans_counted_once_each(traced):
    events, _ = traced
    counts = {name: len(_spans(events, name)) for name in (
        spans.INIT, *INIT_CHILDREN, spans.INIT_KMEANS, spans.WARMUP,
        spans.ROUND, *ROUND_CHILDREN)}
    evals = ROUNDS // EVAL_EVERY
    assert counts == {
        spans.INIT: 1, spans.INIT_GRADIENTS: 1, spans.INIT_GRAM: 1,
        spans.INIT_WEIGHTS: 1, spans.INIT_SLAB: 1,
        spans.INIT_KMEANS: 0,  # full personalization clusters nothing
        spans.WARMUP: 1, spans.ROUND: ROUNDS, spans.COHORT: ROUNDS,
        spans.DISPATCH: ROUNDS, spans.CHECK_FINITE: evals,
        spans.EVALUATE: evals}


def test_host_spans_nest(traced):
    events, _ = traced
    init, rounds = _spans(events, spans.INIT), _spans(events, spans.ROUND)
    for child in INIT_CHILDREN:
        assert all(_inside(s, init) for s in _spans(events, child))
    for child in ROUND_CHILDREN:
        assert all(_inside(s, rounds) for s in _spans(events, child))
    # init, warm-up and the rounds follow one another without overlap
    (_, init_end), = init
    (warm_start, warm_end), = _spans(events, spans.WARMUP)
    assert init_end <= warm_start and warm_end <= min(s for s, _ in rounds)


def test_round_program_stages_in_the_trace(traced):
    _, table = traced
    round_stages = [set(v.values()) for k, v in table.items()
                    if k.startswith("jit__masked")]
    assert round_stages == [{spans.GATHER, spans.LOCAL_SGD, spans.MIX}]


VARIANTS = {
    "ucfl": ("ucfl", {}, ()),
    "ucfl_transport": ("ucfl", {"transport": TransportConfig("int8")},
                       (spans.WIRE,)),
    "ucfl_w_refresh": ("ucfl", {"w_refresh": RefreshConfig()},
                       (spans.W_REFRESH,)),
    "fedavg": ("fedavg", {}, ()),
    "fedavg_faults": ("fedavg",
                      {"faults": faults_lib.FaultConfig(byzantine_frac=0.25)},
                      (spans.UPLOAD,)),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_round_program_carries_stage_scopes(variant):
    """Every scope of the variant's round appears in its lowered HLO's
    metadata, and no other ``fl.`` stage does."""
    name, fed, extra = VARIANTS[variant]
    data, _ = _setup()
    strategy = _strategy(name, **fed)
    key = jax.random.PRNGKey(1)
    state = strategy.init(key, data)
    cohort = Cohort(indices=np.array([0, 2, 4, 6], np.int32),
                    mask=np.array([True, True, True, False]))
    text = jax.jit(
        lambda st, k: strategy.round(st, data, k, cohort)[0]
    ).lower(state, key).as_text(debug_info=True)
    found = set(re.findall(r'[/"](fl\.[a-z_]+)/', text))
    assert found == {spans.GATHER, spans.LOCAL_SGD, spans.MIX, *extra}


def _init_lowering(stage):
    """The lowered text of the init program that runs under ``stage``."""
    from repro.core import ucfl
    from repro.kernels import ops

    data, params0 = _setup()
    if stage == spans.INIT_GRADIENTS:
        return ucfl._special_round_uploads.lower(
            params0, data.x, data.y, apply_fn=lenet.apply, var_batch_size=20,
            chunk_size=4, mesh=None).as_text(debug_info=True)
    g = np.ones((6, 300), np.float32)
    if stage == spans.INIT_GRAM:
        return jax.jit(lambda g: ops.gram(g, impl="interpret", stage=stage)
                       ).lower(g).as_text(debug_info=True)
    return ucfl._delta_and_weights.lower(
        np.eye(6, dtype=np.float32), np.ones(6, np.float32),
        data.n).as_text(debug_info=True)


@pytest.mark.parametrize("stage", [spans.INIT_GRADIENTS, spans.INIT_GRAM,
                                   spans.INIT_WEIGHTS])
def test_init_programs_carry_stage_scopes(stage):
    """Each of ucfl's init programs carries the device stage of the host
    span that launches it, and no other ``fl.`` stage."""
    text = _init_lowering(stage)
    assert set(re.findall(r'[/"](fl\.[a-z_.]+)/', text)) == {stage}
