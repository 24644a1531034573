"""Two-tier hierarchical round engine tests.

Covers the topology PR's guarantees:
  (a) ``FedConfig.topology=None`` is BIT-EXACT with the default config
      for every supporting strategy — the knob is strictly opt-in.
  (b) a two-tier round (per-edge tier-1 masked mix, tier-2 combine at
      the PS) matches the flat round within float association
      (rtol=1e-5) for fedavg, fedprox, and clustered ucfl — the tiered
      rules factorize the flat linear mixes exactly.
  (c) one compiled round shape: a varying-availability trace under a
      tiered strategy still hits ONE masked-round compilation (the edge
      partition is a static-shape argsort/scatter inside the jit), and
      the tiered round composes with a device mesh.
  (d) :func:`repro.federated.topology.edge_partition` preserves the
      cohort invariants per edge: real slots form a prefix, members stay
      strictly increasing, every real cohort slot lands on exactly one
      edge, pads carry sentinels (property-tested under hypothesis).
  (e) the ``pareto`` sampler (``FedConfig.selection``): zero-mass
      clients are never drawn, cohorts obey the padded-prefix contract,
      and the fairness lane bounds every positive-mass client's
      selection gap to ``n_pos`` rounds.
  (f) capability boundaries: strategies whose PS rule cannot factorize
      over per-edge partial sums reject the knob at CONSTRUCTION with a
      NotImplementedError capability note; topology x shard_state /
      async_buffer raise likewise; the dense (cohort=None) path raises
      ValueError; a non-Topology value raises TypeError.

Run multi-device on CPU with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest tests/test_topology.py
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import FedConfig, REGISTRY, ucfl
from repro.core.similarity import RefreshConfig
from repro.data import synthetic
from repro.federated import participation as pp
from repro.federated import simulation
from repro.federated import topology as topo_lib
from repro.federated.async_buffer import AsyncConfig
from repro.federated.participation import (Cohort, ParticipationConfig,
                                           SelectionConfig)
from repro.federated.topology import Topology
from repro.models import lenet
from tests.hypothesis_compat import given, load_ci_profile, st

load_ci_profile(max_examples=25)

NDEV = jax.device_count()


@functools.lru_cache(maxsize=1)
def _setup():
    key = jax.random.PRNGKey(17)
    dkey, mkey = jax.random.split(key)
    data = synthetic.concept_shift(dkey, m=8, n=120, n_test=30,
                                   num_classes=6, groups=2, hw=(16, 16),
                                   channels=1, noise=1.0)
    params0 = lenet.init(mkey, input_hw=(16, 16), channels=1, num_classes=6)
    return data, params0


def _cfg(**kw):
    return FedConfig(lr=0.1, momentum=0.9, epochs=1, batch_size=40, **kw)


def _make(name, params0, cfg):
    if name == "clustered":
        return ucfl.make_ucfl(lenet.apply, params0, cfg, num_streams=2,
                              var_batch_size=40)
    return REGISTRY[name](lenet.apply, params0, cfg)


def _leaves(strat, state):
    return [np.asarray(x) for x in jax.tree.leaves(strat.eval_params(state))]


_COHORT = Cohort(indices=np.asarray([1, 4, 6, 8], np.int32),
                 mask=np.asarray([1, 1, 1, 0], bool))
_TOPO3 = Topology.contiguous(8, 3)
TIERED = ("fedavg", "fedprox", "clustered")


# ----------------------------------------------------- Topology validation

def test_topology_validates():
    with pytest.raises(ValueError, match="num_edges"):
        Topology((0, 0), 0)
    with pytest.raises(ValueError, match="edge ids"):
        Topology((0, 3), 2)
    with pytest.raises(ValueError, match="at least one client"):
        Topology((), 2)


def test_topology_builders():
    t = Topology.from_labels([1, 0, 2, 1])
    assert t.num_edges == 3 and t.num_clients == 4
    t = Topology.contiguous(8, 3)
    assert t.num_clients == 8 and set(t.edge_of) == {0, 1, 2}
    # contiguous blocks: edge ids are nondecreasing
    assert list(t.edge_of) == sorted(t.edge_of)
    # slots bound: min(cohort slots, largest edge population)
    assert t.slots_per_edge(2) == 2
    assert t.slots_per_edge(8) == max(
        np.bincount(np.asarray(t.edge_of)))
    with pytest.raises(ValueError, match="assigns 8 clients"):
        t.check_clients(5, "fedavg")


# --------------------------------------------- (a) topology=None bit-exact

@pytest.mark.parametrize("name", TIERED)
def test_topology_none_bit_exact(name):
    """``topology=None`` must be indistinguishable from the default —
    same strategy, same round, bit-for-bit."""
    data, params0 = _setup()
    rkey = jax.random.PRNGKey(101)
    base = _make(name, params0, _cfg())
    none = _make(name, params0, _cfg(topology=None))
    s0 = base.init(jax.random.PRNGKey(3), data)
    s0n = none.init(jax.random.PRNGKey(3), data)
    sb, _ = base.round(simulation.donation_safe_copy(s0), data, rkey, _COHORT)
    sn, _ = none.round(simulation.donation_safe_copy(s0n), data, rkey,
                       _COHORT)
    for a, b in zip(_leaves(base, sb), _leaves(none, sn)):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------ (b) tiered == flat mix

@pytest.mark.parametrize("name", TIERED)
def test_tiered_matches_flat(name):
    """The two-tier factorization equals the flat mix up to float
    association (normalized per-edge partial sums, tier-2 reweight)."""
    data, params0 = _setup()
    rkey = jax.random.PRNGKey(101)
    flat = _make(name, params0, _cfg())
    tier = _make(name, params0, _cfg(topology=_TOPO3))
    s0 = flat.init(jax.random.PRNGKey(3), data)
    s0t = tier.init(jax.random.PRNGKey(3), data)
    sf, mf = flat.round(simulation.donation_safe_copy(s0), data, rkey,
                        _COHORT)
    st_, mt = tier.round(simulation.donation_safe_copy(s0t), data, rkey,
                         _COHORT)
    assert int(mf["streams"]) == int(mt["streams"])
    for a, b in zip(_leaves(flat, sf), _leaves(tier, st_)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_tiered_composes_with_w_refresh():
    """The streaming W refresh feeds the SAME tiered serve — one code
    path; the refreshed round must run and stay finite."""
    data, params0 = _setup()
    strat = ucfl.make_ucfl(lenet.apply, params0,
                           _cfg(topology=_TOPO3, w_refresh=RefreshConfig()),
                           num_streams=2, var_batch_size=40)
    s0 = strat.init(jax.random.PRNGKey(3), data)
    s1, _ = strat.round(s0, data, jax.random.PRNGKey(101), _COHORT)
    for leaf in _leaves(strat, s1):
        assert np.isfinite(leaf).all()


def test_tiered_multi_round_stays_close_to_flat():
    """Association error must not compound over a training run: after 4
    rounds the tiered clustered trajectory still tracks flat."""
    data, params0 = _setup()
    flat = _make("clustered", params0, _cfg())
    tier = _make("clustered", params0, _cfg(topology=_TOPO3))
    sf = flat.init(jax.random.PRNGKey(3), data)
    st_ = tier.init(jax.random.PRNGKey(3), data)
    key = jax.random.PRNGKey(7)
    for rnd in range(1, 5):
        key, rkey = jax.random.split(key)
        co = pp.sample_cohort(ParticipationConfig(cohort_size=5, seed=9),
                              rnd, data.num_clients, data.n)
        sf, _ = flat.round(sf, data, rkey, co)
        st_, _ = tier.round(st_, data, rkey, co)
    for a, b in zip(_leaves(flat, sf), _leaves(tier, st_)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# --------------------------------------------- (c) one-compilation guard

@pytest.mark.parametrize("name", ["fedavg", "clustered"])
def test_tiered_availability_compiles_once(name):
    """Varying eligible-set sizes under a tiered strategy must reuse ONE
    compiled masked round — the edge partition is shape-static."""
    data, params0 = _setup()
    m = data.num_clients
    trace = np.zeros((m, 3), bool)
    trace[:4, 0] = True
    trace[:2, 1] = True
    trace[:, 2] = True
    part = ParticipationConfig(cohort_size=4, sampler="availability",
                               availability=trace)
    strat = _make(name, params0, _cfg(topology=Topology.contiguous(m, 2)))
    assert strat.round.masked_jit is not None
    simulation.run(strat, lenet.apply, data, jax.random.PRNGKey(1),
                   rounds=6, eval_every=6, participation=part)
    assert strat.round.masked_jit._cache_size() == 1


@pytest.mark.skipif(NDEV < 8,
                    reason="needs 8 devices "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")
def test_tiered_composes_with_mesh():
    """Replicated-mesh local SGD + the tiered mix: matches flat within
    the sharding tolerance and compiles once."""
    data, params0 = _setup()
    rkey = jax.random.PRNGKey(101)
    flat = _make("fedavg", params0, _cfg())
    tier = REGISTRY["fedavg"](lenet.apply, params0,
                              _cfg(topology=_TOPO3, mesh="auto"))
    s0 = flat.init(jax.random.PRNGKey(3), data)
    s0t = tier.init(jax.random.PRNGKey(3), data)
    sf, _ = flat.round(simulation.donation_safe_copy(s0), data, rkey,
                       _COHORT)
    st_, _ = tier.round(simulation.donation_safe_copy(s0t), data, rkey,
                        _COHORT)
    for a, b in zip(_leaves(flat, sf), _leaves(tier, st_)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------- (d) edge_partition invariants

def _check_partition(edge_of, num_edges, idx, mask):
    m = len(edge_of)
    c = idx.shape[0]
    topo = Topology(tuple(edge_of), num_edges)
    slots = topo.slots_per_edge(c)
    eidx, emask, eslot = jax.jit(
        topo_lib.edge_partition, static_argnums=(1, 2))(
        topo.edge_array(), num_edges, slots, idx, mask)
    eidx, emask, eslot = (np.asarray(eidx), np.asarray(emask),
                          np.asarray(eslot))
    assert eidx.shape == emask.shape == eslot.shape == (num_edges, slots)
    seen = []
    for e in range(num_edges):
        mk = emask[e]
        # real slots form a prefix
        assert not np.any(mk[1:] & ~mk[:-1])
        members = eidx[e][mk]
        # members strictly increasing, all genuinely on this edge
        if members.size > 1:
            assert np.all(np.diff(members) > 0)
        assert all(edge_of[i] == e for i in members)
        # eslot maps back to the cohort slot holding the same client
        assert np.array_equal(idx[eslot[e][mk]], members)
        # pads carry the sentinels
        assert np.all(eidx[e][~mk] == m)
        assert np.all(eslot[e][~mk] == c)
        seen.extend(members.tolist())
    # every real cohort member lands on exactly one edge
    assert sorted(seen) == sorted(idx[mask].tolist())


def test_edge_partition_concrete():
    idx = np.asarray([0, 2, 3, 7, 8, 8], np.int32)
    mask = np.asarray([1, 1, 1, 1, 0, 0], bool)
    _check_partition([0, 0, 1, 2, 1, 0, 2, 1], 3, idx, mask)
    # an edge with no cohort members, and an all-pad cohort
    _check_partition([0, 0, 0, 0, 0, 0, 0, 2], 3, idx, mask)
    _check_partition([0, 1] * 4, 2,
                     np.full(4, 8, np.int32), np.zeros(4, bool))


@given(st.data())
def test_edge_partition_property(data_st):
    m = data_st.draw(st.integers(2, 12), label="m")
    num_edges = data_st.draw(st.integers(1, 5), label="E")
    edge_of = data_st.draw(
        st.lists(st.integers(0, num_edges - 1), min_size=m, max_size=m),
        label="edge_of")
    c = data_st.draw(st.integers(1, m), label="c")
    take = data_st.draw(st.integers(0, c), label="take")
    members = data_st.draw(
        st.lists(st.integers(0, m - 1), min_size=take, max_size=take,
                 unique=True), label="members")
    idx = np.full(c, m, np.int32)
    idx[:take] = np.sort(np.asarray(members, np.int32))
    mask = np.zeros(c, bool)
    mask[:take] = True
    _check_partition(edge_of, num_edges, idx, mask)


# ------------------------------------------------ (e) pareto selection

def _schedule_members(cfg, rounds, m, n=None):
    return [co.members for co in pp.cohort_schedule(cfg, rounds, m, n)]


def test_pareto_never_draws_zero_mass():
    m = 10
    mass = np.asarray([0, 0, 1, 1, 1, 1, 2, 2, 0, 3], float)
    cfg = ParticipationConfig(
        cohort_size=4, sampler="pareto", seed=5,
        selection=SelectionConfig(compute=mass, bias=2.0))
    dead = {0, 1, 8}
    for members in _schedule_members(cfg, 30, m):
        assert not (set(members.tolist()) & dead)


def test_pareto_fairness_lane_bounds_starvation():
    """Every statically-positive client is selected at least once per
    n_pos rounds — the deterministic lane's worst case."""
    m = 8
    speeds = np.geomspace(0.05, 20.0, m)  # 400x spread: heavy starvation
    cfg = ParticipationConfig(
        cohort_size=2, sampler="pareto", seed=5,
        selection=SelectionConfig(compute=speeds, bias=4.0))
    sched = _schedule_members(cfg, m, m)
    seen = set()
    for members in sched:
        seen |= set(members.tolist())
    assert seen == set(range(m))


def test_pareto_without_fairness_lane_starves():
    """Same sharp bias, lane off: the slowest client is starved within
    the window the lane would have covered — the lane is load-bearing."""
    m = 8
    speeds = np.geomspace(0.05, 20.0, m)
    cfg = ParticipationConfig(
        cohort_size=2, sampler="pareto", seed=5,
        selection=SelectionConfig(compute=speeds, bias=4.0,
                                  fairness_lane=False))
    seen = set()
    for members in _schedule_members(cfg, m, m):
        seen |= set(members.tolist())
    assert 0 not in seen


def test_pareto_battery_gating_and_padding():
    """Battery-gated clients carry zero mass that phase; when fewer than
    cohort_size clients have mass the cohort pads availability-style."""
    m = 6
    battery = np.zeros((m, 2), bool)
    battery[:2, 0] = True   # phase 0: only clients 0, 1
    battery[:, 1] = True    # phase 1: everyone
    cfg = ParticipationConfig(
        cohort_size=4, sampler="pareto", seed=5,
        selection=SelectionConfig(battery=battery))
    sched = pp.cohort_schedule(cfg, 2, m)
    assert sched[0].num_slots == 4 and len(sched[0]) == 2
    assert set(sched[0].members.tolist()) == {0, 1}
    assert len(sched[1]) == 4


def test_pareto_config_validation():
    with pytest.raises(ValueError, match="bias"):
        SelectionConfig(bias=0.0)
    with pytest.raises(ValueError, match="nonnegative"):
        SelectionConfig(compute=np.asarray([1.0, -1.0]))
    with pytest.raises(ValueError, match="SelectionConfig"):
        ParticipationConfig(sampler="pareto")
    with pytest.raises(ValueError, match="data_value"):
        SelectionConfig(data_value=True).static_mass(4)


def test_with_selection_threads_policy():
    sel = SelectionConfig(bias=2.0)
    assert pp.with_selection(None, None) is None
    got = pp.with_selection(None, sel)
    assert got.sampler == "pareto" and got.selection is sel
    base = ParticipationConfig(cohort_size=3, seed=9)
    got = pp.with_selection(base, sel)
    assert got.cohort_size == 3 and got.seed == 9
    assert got.sampler == "pareto" and got.selection is sel


@given(st.data())
def test_pareto_cohort_contract_property(data_st):
    """Any mass profile yields a valid padded cohort: prefix mask,
    strictly increasing members, only positive-mass clients."""
    m = data_st.draw(st.integers(2, 12), label="m")
    c = data_st.draw(st.integers(1, m), label="c")
    mass = np.asarray(data_st.draw(
        st.lists(st.floats(0.0, 10.0), min_size=m, max_size=m),
        label="mass"))
    bias = data_st.draw(st.floats(0.25, 4.0), label="bias")
    cfg = ParticipationConfig(
        cohort_size=c, sampler="pareto", seed=3,
        selection=SelectionConfig(compute=mass, bias=bias))
    for rnd in (1, 2, 7):
        co = pp.sample_cohort(cfg, rnd, m)  # Cohort.__post_init__
        assert co.num_slots == c            # validates the contract
        assert all(mass[i] > 0 for i in co.members)


# --------------------------------------------- (f) capability boundaries

UNSUPPORTED = ("scaffold", "ditto", "pfedme", "fedfomo", "local", "cfl",
               "oracle", "ucfl", "ucfl_parallel")


@pytest.mark.parametrize("name", UNSUPPORTED)
def test_unsupported_strategy_raises_at_construction(name):
    _, params0 = _setup()
    kw = {"var_batch_size": 40} if name.startswith("ucfl") else {}
    with pytest.raises(NotImplementedError, match="topology"):
        REGISTRY[name](lenet.apply, params0, FedConfig(topology=_TOPO3),
                       **kw)


@pytest.mark.parametrize("kw", [dict(shard_state=True),
                                dict(async_buffer=AsyncConfig(flush_k=2))])
def test_noncomposable_knobs_raise(kw):
    _, params0 = _setup()
    with pytest.raises(NotImplementedError, match="topology"):
        REGISTRY["fedavg"](lenet.apply, params0,
                           FedConfig(topology=_TOPO3, **kw))


def test_dense_path_rejects_topology():
    data, params0 = _setup()
    strat = _make("fedavg", params0, _cfg(topology=_TOPO3))
    s0 = strat.init(jax.random.PRNGKey(3), data)
    with pytest.raises(ValueError, match="dense"):
        strat.round(s0, data, jax.random.PRNGKey(101), None)


def test_non_topology_value_raises_typeerror():
    _, params0 = _setup()
    with pytest.raises(TypeError, match="Topology"):
        REGISTRY["fedavg"](lenet.apply, params0,
                           FedConfig(topology=(0, 0, 1, 1)))


def test_topology_client_count_mismatch():
    data, params0 = _setup()
    strat = _make("fedavg", params0, _cfg(topology=Topology.contiguous(5, 2)))
    with pytest.raises(ValueError, match="5 clients"):
        strat.init(jax.random.PRNGKey(3), data)
