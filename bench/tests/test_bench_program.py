"""The system under test is built from the cell's files alone: the
traffic forms that later cells take (another strategy, its arguments,
wire transport, W refresh, an availability trace, a four-chip mesh) turn
into the program's own configuration objects, and the ucfl check refuses
traffic its reference does not model."""
import dataclasses
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import datagen, program, spec  # noqa: E402
from repro.core.similarity import RefreshConfig  # noqa: E402
from repro.federated.transport import TransportConfig  # noqa: E402

BASE = spec.load_cell("paper-ucfl", ROOT)
TRANSPORT = {"transport": {"call": "repro.federated.transport.TransportConfig",
                           "kind": "int8"}}
REFRESH = {"w_refresh": {"call": "repro.core.similarity.RefreshConfig",
                         "alpha": 0.5}}
DIURNAL = {"cohort_size": 4, "sampler": "availability",
           "availability": {"call": "repro.federated.participation."
                                    "diurnal_trace", "m": 20, "period": 24}}


def _cell(chips=1, **traffic):
    return dataclasses.replace(BASE, chips=chips,
                               traffic=dict(BASE.traffic, **traffic))


def test_cell_traffic_builds_the_configured_program():
    cell = BASE
    cfg = program.fed_config(cell.config, cell.traffic, cell.chips)
    assert (cfg.lr, cfg.momentum, cfg.epochs, cfg.batch_size) == (
        cell.config["lr"], cell.config["momentum"],
        cell.config["local_epochs"], cell.config["batch_size"])
    assert cfg.mesh is None and cfg.transport is None
    part = program.participation(cell.traffic, 7)
    assert (part.cohort_size, part.sampler, part.seed) == (10, "uniform", 7)


@pytest.mark.parametrize("chips,traffic,field,want", [
    (4, {"fed_config": {"shard_state": True}}, "mesh", 4),
    (4, {"fed_config": {"shard_state": True}}, "shard_state", True),
    (4, {"fed_config": {"mesh": 2}}, "mesh", 2),
    (1, {"fed_config": TRANSPORT}, "transport",
     TransportConfig(kind="int8")),
    (1, {"fed_config": REFRESH}, "w_refresh", RefreshConfig(alpha=0.5)),
], ids=["mesh4", "shard_state", "mesh_set", "int8", "refresh"])
def test_fed_config_from_traffic(chips, traffic, field, want):
    cell = _cell(chips, **traffic)
    cfg = program.fed_config(cell.config, cell.traffic, cell.chips)
    assert getattr(cfg, field) == want


def test_availability_trace_from_traffic():
    part = program.participation(_cell(participation=DIURNAL).traffic, 3)
    assert part.sampler == "availability" and part.cohort_size == 4
    assert isinstance(part.availability, np.ndarray)
    assert part.availability.shape == (20, 24)


@pytest.mark.parametrize("strategy,args", [
    ("ucfl", {"var_batch_size": 50, "num_streams": 2}),
    ("fedavg", {}),
], ids=["ucfl_k2", "fedavg"])
def test_strategy_and_its_arguments_from_traffic(strategy, args):
    conf = dict(BASE.config, clients=4, train_per_client=100,
                test_per_client=20)
    _, params0 = datagen.generate(conf, 5)
    cell = dataclasses.replace(_cell(strategy=strategy, strategy_args=args),
                               config=conf)
    built = program.build(cell, params0)
    assert callable(built.round) and callable(built.init)


@pytest.mark.parametrize("traffic", [
    {"fed_config": TRANSPORT}, {"participation": DIURNAL},
    {"strategy_args": {"var_batch_size": 50, "num_streams": 2}},
    {"strategy": "fedavg", "strategy_args": {}},
], ids=["int8", "diurnal", "ucfl_k2", "fedavg"])
def test_ucfl_check_refuses_traffic_it_does_not_model(traffic):
    cell = _cell(**traffic)
    with pytest.raises(ValueError, match="does not model"):
        spec.checker(cell).numbers(cell, None, None, None, None, 0)
