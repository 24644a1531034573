"""Shared benchmark scaffolding.

Paper-scale settings (m=20/100, 5 trials, LeNet-5 on EMNIST/CIFAR) are
reproduced in *structure*; the default "fast" scale is sized for a
1-core CPU host. ``--full`` restores paper-scale m/rounds/trials.
"""
from __future__ import annotations

import dataclasses
import functools

import jax

from repro.core import FedConfig, REGISTRY, ucfl
from repro.data import synthetic
from repro.federated import simulation
from repro.models import lenet


@dataclasses.dataclass(frozen=True)
class BenchScale:
    m: int = 8
    n: int = 150
    n_test: int = 40
    num_classes: int = 8
    hw: tuple = (16, 16)
    rounds: int = 10
    trials: int = 1
    groups: int = 4
    batch_size: int = 50
    var_batch: int = 50


FAST = BenchScale()
FULL = BenchScale(m=20, n=500, n_test=100, num_classes=20, hw=(28, 28),
                  rounds=60, trials=5)


def scenario_data(name: str, key, s: BenchScale):
    if name == "label_shift":
        return synthetic.label_shift(
            key, m=s.m, n=s.n, n_test=s.n_test, num_classes=s.num_classes,
            alpha=0.4, hw=s.hw)
    if name == "covariate_label_shift":
        return synthetic.covariate_label_shift(
            key, m=s.m, n=s.n, n_test=s.n_test, num_classes=s.num_classes,
            alpha=8.0, groups=s.groups, hw=s.hw)
    if name == "concept_shift":
        return synthetic.concept_shift(
            key, m=s.m, n=s.n, n_test=s.n_test,
            num_classes=max(s.num_classes, 6) if s.hw[0] <= 16 else 10,
            groups=s.groups, hw=s.hw, channels=1, noise=0.8)
    raise ValueError(name)


def make_params0(key, s: BenchScale, num_classes=None):
    return lenet.init(key, input_hw=s.hw, channels=1,
                      num_classes=num_classes or s.num_classes)


def make_strategy(name: str, params0, s: BenchScale, *, chunk_size=None,
                  mesh=None, shard_state=False, w_refresh=None,
                  async_buffer=None, faults=None, robust=None, transport=None,
                  topology=None, selection=None, **kw):
    cfg = FedConfig(batch_size=s.batch_size, chunk_size=chunk_size, mesh=mesh,
                    shard_state=shard_state, w_refresh=w_refresh,
                    async_buffer=async_buffer,
                    faults=faults, robust=robust, transport=transport,
                    topology=topology, selection=selection)
    if name == "ucfl":
        return ucfl.make_ucfl(lenet.apply, params0, cfg,
                              var_batch_size=s.var_batch, **kw)
    if name.startswith("ucfl_k"):
        return ucfl.make_ucfl(lenet.apply, params0, cfg,
                              num_streams=int(name[6:]),
                              var_batch_size=s.var_batch, **kw)
    if name == "ucfl_parallel":
        return REGISTRY["ucfl_parallel"](lenet.apply, params0, cfg,
                                         var_batch_size=s.var_batch)
    if name in ("scaffold", "pfedme"):
        # keep each maker's paper-footnote local-solver defaults (lr,
        # momentum, epochs, batch size) but thread the ENGINE knobs —
        # dropping cfg here used to silently ignore transport/mesh/faults
        import inspect

        base = inspect.signature(REGISTRY[name]).parameters["cfg"].default
        cfg = dataclasses.replace(
            base, chunk_size=chunk_size, mesh=mesh, shard_state=shard_state,
            w_refresh=w_refresh,
            async_buffer=async_buffer, faults=faults, robust=robust,
            transport=transport, topology=topology, selection=selection)
        return REGISTRY[name](lenet.apply, params0, cfg, **kw)
    return REGISTRY[name](lenet.apply, params0, cfg, **kw)


def num_classes_for(scenario: str, s: BenchScale) -> int:
    if scenario == "concept_shift" and s.hw[0] <= 16:
        return max(s.num_classes, 6)
    return s.num_classes


def run_trials(scenario: str, strat_name: str, s: BenchScale, *, seed=0,
               participation=None, **kw):
    """Mean/std over trials of the (avg, worst) pair at the argmax-avg
    round (one model per trial, matching Tables 1/2)."""
    import numpy as np

    finals, worsts, hists = [], [], []
    for t in range(s.trials):
        key = jax.random.PRNGKey(seed + 997 * t)
        dkey, mkey, skey = jax.random.split(key, 3)
        data = scenario_data(scenario, dkey, s)
        params0 = make_params0(mkey, s, num_classes_for(scenario, s))
        strat = make_strategy(strat_name, params0, s, **kw)
        h = simulation.run(strat, lenet.apply, data, skey, rounds=s.rounds,
                           eval_every=max(s.rounds // 4, 1),
                           participation=participation)
        avg, worst = h.paired_best
        finals.append(avg)
        worsts.append(worst)
        hists.append(h)
    return {
        "avg": float(np.mean(finals)), "avg_std": float(np.std(finals)),
        "worst": float(np.mean(worsts)),
        # the worst-node headline needs its spread alongside avg_std
        "worst_std": float(np.std(worsts)), "hists": hists,
    }


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.2f},{derived}"
