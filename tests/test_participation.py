"""Partial-participation engine tests.

Covers: (a) the fraction=1.0 regression — the explicit-cohort round path
must reproduce the dense full-participation path for ucfl, fedavg, and
clustered ucfl; (b) sampler contracts; (c) absent clients keeping their
last model; (d) the chunked client axis matching the monolithic vmap; and
(e) the m=128 / fraction=0.1 / chunk_size=16 scale target on CPU.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedConfig, REGISTRY, ucfl
from repro.data import synthetic
from repro.federated import client as fedclient
from repro.federated import simulation
from repro.federated.participation import (Cohort, ParticipationConfig,
                                           as_cohort, pad_slots,
                                           sample_cohort)
from tests.hypothesis_compat import given, st
from repro.models import lenet


@functools.lru_cache(maxsize=1)
def _setup():
    key = jax.random.PRNGKey(17)
    dkey, mkey = jax.random.split(key)
    data = synthetic.concept_shift(dkey, m=8, n=120, n_test=30,
                                   num_classes=6, groups=2, hw=(16, 16),
                                   channels=1, noise=1.0)
    params0 = lenet.init(mkey, input_hw=(16, 16), channels=1, num_classes=6)
    cfg = FedConfig(lr=0.1, momentum=0.9, epochs=1, batch_size=40)
    return data, params0, cfg


def _make(name, params0, cfg):
    if name == "ucfl":
        return ucfl.make_ucfl(lenet.apply, params0, cfg, var_batch_size=40)
    if name == "clustered":
        return ucfl.make_ucfl(lenet.apply, params0, cfg, num_streams=2,
                              var_batch_size=40)
    return REGISTRY[name](lenet.apply, params0, cfg)


# ---------------------------------------------------------------- regression

@pytest.mark.parametrize("name", ["ucfl", "fedavg", "clustered"])
def test_full_cohort_matches_dense_path(name):
    """round(..., cohort=arange(m)) == round(..., cohort=None) per round."""
    data, params0, cfg = _setup()
    strat = _make(name, params0, cfg)
    state_a = strat.init(jax.random.PRNGKey(3), data)
    state_b = state_a
    cohort = np.arange(data.num_clients, dtype=np.int32)
    for rnd in range(2):
        rkey = jax.random.PRNGKey(100 + rnd)
        state_a, _ = strat.round(state_a, data, rkey)
        state_b, _ = strat.round(state_b, data, rkey, cohort)
        for a, b in zip(jax.tree.leaves(strat.eval_params(state_a)),
                        jax.tree.leaves(strat.eval_params(state_b))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_full_cohort_matches_dense_path_all_strategies(name):
    """One-round full-cohort equivalence for every registered strategy —
    locks in the 8 hand-rewritten baseline cohort paths too."""
    data, params0, cfg = _setup()
    make = REGISTRY[name]
    strat = (make(lenet.apply, params0) if name in ("scaffold", "pfedme")
             else make(lenet.apply, params0, cfg))
    state = strat.init(jax.random.PRNGKey(3), data)
    rkey = jax.random.PRNGKey(101)
    state_a, _ = strat.round(state, data, rkey)
    state_b, _ = strat.round(state, data, rkey,
                             np.arange(data.num_clients, dtype=np.int32))
    for a, b in zip(jax.tree.leaves(strat.eval_params(state_a)),
                    jax.tree.leaves(strat.eval_params(state_b))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fraction_one_is_dense_fast_path():
    """fraction=1.0 resolves to cohort=None — bit-exact by construction."""
    cfg = ParticipationConfig(fraction=1.0)
    assert sample_cohort(cfg, 1, 16) is None
    assert sample_cohort(None, 1, 16) is None


# ------------------------------------------------------------------ samplers

def test_uniform_sampler_contract():
    cfg = ParticipationConfig(fraction=0.25)
    for rnd in range(1, 6):
        c = sample_cohort(cfg, rnd, 32)
        assert c.num_slots == 8 and len(c) == 8  # no pad slots needed
        assert c.indices.dtype == np.int32 and c.mask.all()
        assert (np.diff(c.members) > 0).all()  # sorted, unique
        assert c.members.min() >= 0 and c.members.max() < 32
    # reproducible for a fixed round, different across rounds
    np.testing.assert_array_equal(sample_cohort(cfg, 3, 32).indices,
                                  sample_cohort(cfg, 3, 32).indices)
    assert not np.array_equal(sample_cohort(cfg, 1, 32).indices,
                              sample_cohort(cfg, 2, 32).indices)


def test_weighted_sampler_biases_by_n():
    cfg = ParticipationConfig(cohort_size=4, sampler="weighted")
    n = np.asarray([1.0] * 15 + [1000.0])
    hits = sum(15 in sample_cohort(cfg, r, 16, n).members
               for r in range(1, 101))
    assert hits > 95  # client 15 holds ~98.5% of the mass


def test_round_robin_covers_everyone():
    cfg = ParticipationConfig(cohort_size=3, sampler="round_robin")
    seen = set()
    for rnd in range(1, 5):  # ceil(10/3) = 4 rounds for full coverage
        seen.update(sample_cohort(cfg, rnd, 10).members.tolist())
    assert seen == set(range(10))


def test_availability_sampler_respects_trace():
    trace = np.zeros((6, 2), bool)
    trace[:3, 0] = True  # clients 0..2 up on even phases
    trace[3:, 1] = True  # clients 3..5 up on odd phases
    cfg = ParticipationConfig(cohort_size=2, sampler="availability",
                              availability=trace)
    assert set(sample_cohort(cfg, 1, 6).members) <= {0, 1, 2}  # (rnd-1)%2==0
    assert set(sample_cohort(cfg, 2, 6).members) <= {3, 4, 5}


def test_availability_pads_to_fixed_shape():
    """Short eligible sets are padded with masked sentinel slots, so every
    round presents ONE static cohort shape to jit."""
    trace = np.zeros((8, 2), bool)
    trace[:2, 0] = True   # only 2 of 8 up on phase 0
    trace[:, 1] = True    # everyone up on phase 1
    cfg = ParticipationConfig(cohort_size=5, sampler="availability",
                              availability=trace)
    short, full = sample_cohort(cfg, 1, 8), sample_cohort(cfg, 2, 8)
    assert short.num_slots == full.num_slots == 5
    assert len(short) == 2 and len(full) == 5
    np.testing.assert_array_equal(short.indices[2:], [8, 8, 8])  # sentinel m
    np.testing.assert_array_equal(short.mask, [1, 1, 0, 0, 0])
    np.testing.assert_array_equal(short.members, [0, 1])


def test_availability_nobody_online_skips_round():
    """An all-offline phase yields an all-masked cohort and the engine
    idles."""
    trace = np.zeros((8, 2), bool)
    trace[:, 0] = True  # everyone up on phase 0, nobody on phase 1
    cfg = ParticipationConfig(cohort_size=3, sampler="availability",
                              availability=trace)
    assert len(sample_cohort(cfg, 2, 8)) == 0

    data, params0, fcfg = _setup()
    strat = _make("fedavg", params0, fcfg)
    h = simulation.run(strat, lenet.apply, data, jax.random.PRNGKey(1),
                       rounds=2, eval_every=1, participation=cfg)
    assert h.metrics[0]["cohort_size"] == 3  # phase 0: trained
    assert h.metrics[1] == {"streams": 0, "cohort_size": 0, "skipped": True}
    # the skipped round must not change any model
    assert h.avg_acc[1] == h.avg_acc[0]


def test_config_validation():
    with pytest.raises(ValueError):
        ParticipationConfig(fraction=0.0)
    with pytest.raises(ValueError):
        ParticipationConfig(sampler="nope")
    with pytest.raises(ValueError):
        ParticipationConfig(sampler="availability")


@pytest.mark.parametrize("fraction,m,want", [
    # half-way fractions: int(round(...)) banker's-rounded these DOWN
    # (0.25*10 = 2.5 -> 2); the explicit ceil rule provisions at least
    # the requested participation fraction
    (0.25, 10, 3),
    (0.5, 5, 3),
    (0.75, 10, 8),
    (0.05, 10, 1),
    (0.125, 4, 1),
    # exact targets stay exact, including ones float fuzz pushes just
    # above an integer (0.1 * 130 == 13.000000000000002)
    (0.5, 8, 4),
    (0.1, 130, 13),
    (0.1, 128, 13),
    (1.0, 7, 7),
])
def test_resolve_size_ceil_rule(fraction, m, want):
    assert ParticipationConfig(fraction=fraction).resolve_size(m) == want


def test_resolve_size_explicit_cohort_size_clamps():
    assert ParticipationConfig(cohort_size=5).resolve_size(3) == 3
    assert ParticipationConfig(cohort_size=5).resolve_size(20) == 5


def test_pad_slots_rejects_shrinking():
    c = Cohort(indices=np.asarray([1, 4, 6], np.int32),
               mask=np.ones(3, bool))
    assert pad_slots(c, 3, m=8) is c  # equal size stays a no-op
    with pytest.raises(ValueError, match="only extends"):
        pad_slots(c, 2, m=8)


def test_weighted_sampler_all_zero_sizes_raises():
    cfg = ParticipationConfig(cohort_size=2, sampler="weighted")
    with pytest.raises(ValueError, match="zero dataset size"):
        sample_cohort(cfg, 1, 4, np.zeros(4))


def test_weighted_sampler_few_positive_takes_them_all():
    """Fewer positive-mass clients than slots: the whole positive set
    participates and the remaining slots are masked pads (rng.choice
    used to crash; a renormalized p used to emit NaNs on sum 0)."""
    cfg = ParticipationConfig(cohort_size=4, sampler="weighted")
    n = np.asarray([0.0, 3.0, 0.0, 0.0, 2.0, 0.0])
    c = sample_cohort(cfg, 1, 6, n)
    assert c.num_slots == 4 and len(c) == 2
    np.testing.assert_array_equal(c.members, [1, 4])
    np.testing.assert_array_equal(c.indices[2:], [6, 6])


def test_weighted_sampler_never_draws_zero_mass_clients():
    cfg = ParticipationConfig(cohort_size=2, sampler="weighted")
    n = np.asarray([1.0, 0.0, 1.0, 0.0, 1.0, 0.0])
    for rnd in range(1, 30):
        assert set(sample_cohort(cfg, rnd, 6, n).members) <= {0, 2, 4}


# ------------------------------------------------------- cohort invariants

def test_cohort_validates_shapes_and_prefix():
    with pytest.raises(ValueError, match="same length"):
        Cohort(indices=np.asarray([1, 2, 3], np.int32),
               mask=np.asarray([True, True], bool))
    with pytest.raises(ValueError, match="sorted prefix"):
        Cohort(indices=np.asarray([1, 8, 3], np.int32),
               mask=np.asarray([True, False, True], bool))
    with pytest.raises(ValueError, match="strictly increasing"):
        Cohort(indices=np.asarray([4, 1, 8], np.int32),
               mask=np.asarray([True, True, False], bool))
    with pytest.raises(ValueError, match="strictly increasing"):
        Cohort(indices=np.asarray([4, 4], np.int32),
               mask=np.asarray([True, True], bool))


@given(st.sets(st.integers(min_value=0, max_value=31), min_size=1,
               max_size=16),
       st.integers(min_value=0, max_value=8))
def test_pad_slots_and_as_cohort_preserve_members(members, extra):
    members = np.sort(np.asarray(sorted(members), np.int32))
    m = 32
    c = as_cohort(members, m)
    np.testing.assert_array_equal(c.members, members)  # as_cohort exact
    p = pad_slots(c, c.num_slots + extra, m)
    np.testing.assert_array_equal(p.members, members)  # padding exact
    assert p.num_slots == c.num_slots + extra
    assert not p.mask[len(members):].any()
    assert (p.indices[len(members):] == m).all()


# ------------------------------------------------------- engine invariants

def test_absent_clients_keep_last_model():
    data, params0, cfg = _setup()
    strat = _make("ucfl", params0, cfg)
    state = strat.init(jax.random.PRNGKey(3), data)
    # snapshot to host BEFORE the round: the masked round donates the
    # stacked-params buffer, so the device copy dies with the call
    before = [np.asarray(leaf) for leaf in
              jax.tree.leaves(strat.eval_params(state))]
    cohort = np.asarray([1, 4, 6], np.int32)
    absent = np.asarray([0, 2, 3, 5, 7])
    new_state, metrics = strat.round(state, data, jax.random.PRNGKey(5),
                                     cohort)
    after = strat.eval_params(new_state)
    assert metrics["cohort_size"] == 3
    for a, b in zip(before, jax.tree.leaves(after)):
        np.testing.assert_array_equal(a[absent], np.asarray(b)[absent])
        assert np.abs(a[cohort] - np.asarray(b)[cohort]).max() > 0


def test_partial_run_all_strategies_finite():
    data, params0, cfg = _setup()
    part = ParticipationConfig(fraction=0.5)
    for name in sorted(REGISTRY):
        make = REGISTRY[name]
        strat = (make(lenet.apply, params0) if name in ("scaffold", "pfedme")
                 else make(lenet.apply, params0, cfg))
        h = simulation.run(strat, lenet.apply, data, jax.random.PRNGKey(1),
                           rounds=2, eval_every=2, participation=part)
        assert 0.0 <= h.final_avg <= 1.0
        assert h.metrics[-1]["cohort_size"] == 4


# ------------------------------------------------------------------ chunking

def test_chunked_local_sgd_matches_vmap():
    data, params0, cfg = _setup()
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (data.num_clients,) + x.shape) + 0.0,
        params0)
    key = jax.random.PRNGKey(9)
    dense = fedclient.make_federated_local_sgd(
        lenet.apply, lr=0.1, momentum=0.9, epochs=1, batch_size=40)
    for chunk in (3, 4, 8, 16):  # non-dividing, dividing, exact, oversize
        chunked = fedclient.make_federated_local_sgd(
            lenet.apply, lr=0.1, momentum=0.9, epochs=1, batch_size=40,
            chunk_size=chunk)
        a, _ = dense(stacked, data.x, data.y, key)
        b, _ = chunked(stacked, data.x, data.y, key)
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-6, atol=1e-7)


def test_pfedme_honors_chunk_size():
    """pfedme's custom client loop must respect the FedConfig memory knob."""
    data, params0, _ = _setup()
    dense = REGISTRY["pfedme"](lenet.apply, params0)
    chunked = REGISTRY["pfedme"](
        lenet.apply, params0,
        FedConfig(lr=0.01, momentum=0.0, epochs=1, batch_size=20,
                  chunk_size=3))
    sa = dense.init(jax.random.PRNGKey(3), data)
    sb = chunked.init(jax.random.PRNGKey(3), data)
    sa, _ = dense.round(sa, data, jax.random.PRNGKey(5))
    sb, _ = chunked.round(sb, data, jax.random.PRNGKey(5))
    for a, b in zip(jax.tree.leaves(dense.eval_params(sa)),
                    jax.tree.leaves(chunked.eval_params(sb))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


def test_scale_target_m128_fraction01_chunk16():
    """The acceptance-scale run: m=128, fraction=0.1, chunk_size=16."""
    key = jax.random.PRNGKey(0)
    dkey, mkey = jax.random.split(key)
    data = synthetic.label_shift(dkey, m=128, n=50, n_test=10,
                                 num_classes=4, alpha=1.0, hw=(16, 16))
    params0 = lenet.init(mkey, input_hw=(16, 16), channels=1, num_classes=4)
    cfg = FedConfig(lr=0.1, momentum=0.9, epochs=1, batch_size=25,
                    chunk_size=16)
    strat = REGISTRY["fedavg"](lenet.apply, params0, cfg)
    part = ParticipationConfig(fraction=0.1)
    h = simulation.run(strat, lenet.apply, data, jax.random.PRNGKey(1),
                       rounds=2, eval_every=2, participation=part,
                       warmup=False)
    assert h.metrics[-1]["cohort_size"] == 13
    assert 0.0 <= h.final_avg <= 1.0


# ------------------------------------------------- reporting satellites

def test_wall_s_excludes_eval_time(monkeypatch):
    """History.wall_s must measure steady-state ROUNDS only — eval
    frequency is a measurement choice, and it used to leak into the
    timer. A deliberately slow (stubbed) evaluate must land in eval_s,
    not wall_s."""
    import time
    import types

    from repro.core.strategy import Strategy

    def slow_eval(apply_fn, params, x, y, batch=None, mesh=None):
        time.sleep(0.2)
        return np.zeros(4)

    monkeypatch.setattr(simulation, "evaluate", slow_eval)
    strat = Strategy("stub", init=lambda key, data: {"p": jnp.zeros(())},
                     round=lambda s, d, k, c=None: (s, {"streams": 0}),
                     eval_params=lambda s: s["p"])
    data = types.SimpleNamespace(num_clients=4, n=np.ones(4), x=None,
                                 y=None, x_test=None, y_test=None)
    h = simulation.run(strat, None, data, jax.random.PRNGKey(0), rounds=3,
                       eval_every=1, warmup=False)
    assert h.eval_s >= 0.55            # three stubbed eval passes
    assert h.wall_s < h.eval_s / 2     # rounds are trivial next to them
    assert len(h.avg_acc) == 3


def test_warmup_leaves_no_compile_on_the_round_clock(monkeypatch):
    """wall_s is steady state: the warm-up compiles everything round 1
    runs before its eval clock starts, the eval-round finite check and
    the strategy's eval_params included (it used to compile inside the
    first timed round)."""
    import time
    import types

    compiles, marks = [], []

    def listen(event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    def clock():  # 1st call: the round timer starts; 2nd: round 1's eval
        marks.append(len(compiles))
        return time.perf_counter()

    monkeypatch.setattr(simulation, "time",
                        types.SimpleNamespace(perf_counter=clock))
    dkey, mkey = jax.random.split(jax.random.PRNGKey(0))
    data = synthetic.label_shift(dkey, m=4, n=40, n_test=10, num_classes=4,
                                 alpha=0.4, hw=(16, 16))
    params0 = lenet.init(mkey, input_hw=(16, 16), channels=1, num_classes=4)
    strat = ucfl.make_ucfl(lenet.apply, params0,
                           FedConfig(epochs=1, batch_size=20),
                           var_batch_size=20)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        simulation.run(strat, lenet.apply, data, jax.random.PRNGKey(1),
                       rounds=1, participation=ParticipationConfig(
                           cohort_size=2))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert marks[0] > 0 and marks[1] == marks[0]


def test_run_trials_reports_worst_std():
    """The paper's worst-node headline metric ships with its spread:
    run_trials must report worst_std alongside avg_std (regression — it
    silently dropped it)."""
    data_fn = functools.partial(
        synthetic.label_shift, m=4, n=40, n_test=10, num_classes=4,
        alpha=0.4, hw=(16, 16))
    params0 = lenet.init(jax.random.PRNGKey(0), input_hw=(16, 16),
                         channels=1, num_classes=4)
    res = simulation.run_trials(
        lambda t: REGISTRY["fedavg"](lenet.apply, params0,
                                     FedConfig(batch_size=20)),
        lenet.apply, lambda key: data_fn(key), trials=2, rounds=2)
    assert set(res) >= {"avg_mean", "avg_std", "worst_mean", "worst_std"}
    worsts = [h.paired_best[1] for h in res["histories"]]
    assert res["worst_std"] == pytest.approx(float(np.std(worsts)))
    assert res["worst_std"] >= 0.0
