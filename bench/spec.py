"""Read BENCHMARK.json and the files of one cell.

Everything that belongs to one configuration, traffic mix, cell or
metric lives in a file of its own, found by its name:

* ``configs[].file`` — the deployment (sizes, data, local SGD settings);
* ``bench/traffic/<traffic>.json`` — strategy, its arguments, FedConfig
  and participation settings, rounds, cadence, and the check to use;
* ``bench/checks/<check>.py`` — the comparison with the plain reference;
* ``bench/limits/<workload>.json`` — the limits of the correctness check;
* ``bench/metrics/<metric>.py`` — the reader of one metric.

A metric entry with a ``workloads`` key is read only in those cells.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root=ROOT) -> dict:
    return load_json(pathlib.Path(root) / "BENCHMARK.json")


def _covers(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root=ROOT) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files; KeyError when
    BENCHMARK.json has no such workload."""
    root = pathlib.Path(root)
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}[name]
    conf = {c["name"]: c for c in bench["configs"]}[work["config"]]
    return Cell(
        name=name,
        chips=int(work["chips"]),
        config=load_json(root / conf["file"]),
        traffic=load_json(root / "bench" / "traffic"
                          / f"{work['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _covers(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _covers(m, name)),
    )


def module(kind: str, name: str, root=ROOT):
    """The module ``bench/<kind>/<name>.py``, loaded from its file (names
    may hold ``.`` and ``-``)."""
    path = pathlib.Path(root) / "bench" / kind / f"{name}.py"
    tag = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root=ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return module("metrics", metric, root).read


def checker(cell: Cell, root=ROOT):
    """The module of ``bench/checks/<traffic's check>.py``."""
    return module("checks", cell.traffic["check"], root)
