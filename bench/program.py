"""The system under test, built from a cell's files and driven by trials.

A trial is one ``repro.federated.simulation.run``: the strategy's init
(for ucfl, the Eq. 9 collaboration round), a warm-up round, the timed
rounds and the evaluation passes. Every trial of a run reuses one
strategy object on one data set, so no trial traces the round again.

Everything the strategy is built from comes from the cell's files: the
configuration gives the local SGD settings and ``chunk_size``; the
traffic gives the registered ``strategy``, its keyword ``strategy_args``,
further ``fed_config`` fields (transport, W refresh, state sharding, ...)
and the ``participation`` fields (cohort size, sampler, availability).
A cell on more than one chip shards the cohort over all of its chips
(``FedConfig.mesh``) unless its traffic sets the mesh itself. A value
written ``{"call": "module.name", ...}`` is the result of that callable
of the program on the other keys, so that a traffic file can hold a
``TransportConfig`` or an availability trace.

The harness's own host spans (``bench.trial``, ``bench.init``,
``bench.round``, ``bench.eval_params``) wrap the calls into the strategy,
so that the device's idle gaps in a trace can be put down to what the
host was doing.
"""
from __future__ import annotations

import dataclasses
import importlib
import time

import jax

from repro.core import REGISTRY, FedConfig
from repro.data.synthetic import FederatedData
from repro.federated import simulation
from repro.federated.participation import ParticipationConfig
from repro.models import lenet


def _span(name, fn):
    def wrapped(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)
    return wrapped


def construct(value):
    """``value`` with every ``{"call": "module.name", **kwargs}`` in it
    replaced by the result of that call."""
    if isinstance(value, dict):
        value = {k: construct(v) for k, v in value.items()}
        if "call" in value:
            module, _, name = value.pop("call").rpartition(".")
            return getattr(importlib.import_module(module), name)(**value)
        return value
    if isinstance(value, list):
        return [construct(v) for v in value]
    return value


def fed_config(config: dict, traffic: dict, chips: int) -> FedConfig:
    kwargs = {"lr": config["lr"], "momentum": config["momentum"],
              "epochs": config["local_epochs"],
              "batch_size": config["batch_size"],
              "chunk_size": config["chunk_size"]}
    if chips > 1:
        kwargs["mesh"] = chips
    kwargs.update(construct(traffic.get("fed_config", {})))
    return FedConfig(**kwargs)


def participation(traffic: dict, seed: int) -> ParticipationConfig:
    return ParticipationConfig(**construct(traffic["participation"]),
                               seed=seed)


def build(cell, params0):
    """The traffic's registered strategy over the configuration's model."""
    traffic = cell.traffic
    cfg = fed_config(cell.config, traffic, cell.chips)
    strategy = REGISTRY[traffic["strategy"]](
        lenet.apply, params0, cfg, **construct(traffic["strategy_args"]))
    return dataclasses.replace(
        strategy,
        init=_span("bench.init", strategy.init),
        round=_span("bench.round", strategy.round),
        eval_params=_span("bench.eval_params", strategy.eval_params))


def federated_data(arrays: dict) -> FederatedData:
    return FederatedData(arrays["x"], arrays["y"], arrays["x_test"],
                         arrays["y_test"], arrays["group"], arrays["n"])


def run_trial(strategy, data, key, pseed: int, traffic: dict, rounds: int,
              eval_every: int):
    """One whole training run; returns its History and host seconds."""
    part = participation(traffic, pseed)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.trial"):
        hist = simulation.run(strategy, lenet.apply, data, key,
                              rounds=rounds, eval_every=eval_every,
                              participation=part)
    return hist, time.perf_counter() - t0


def timings(hist, host_s: float, rounds: int) -> dict:
    return {"wall_s": hist.wall_s, "eval_s": hist.eval_s, "host_s": host_s,
            "rounds": rounds, "evals": len(hist.rounds)}
