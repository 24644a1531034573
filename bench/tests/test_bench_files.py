"""The benchmark's data files: they load, their names are well formed, and
the command refuses to run where it finds no TPU."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/")
        assert ".." not in word
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    for entry in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert LINE.match(c["why"]) and LINE.match(c["source"])
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in BENCH["workloads"]}) == len(WORKLOADS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_load(workload):
    cell = spec.load_cell(workload, ROOT)
    conf, traf = cell.config, cell.traffic
    entry = {c["name"]: c for c in BENCH["configs"]}[
        {w["name"]: w for w in BENCH["workloads"]}[workload]["config"]]
    assert conf["name"] == entry["name"]
    assert set(conf["reduced"]) == set(entry["reduced"])
    assert traf["participation"]["cohort_size"] <= conf["clients"]
    assert traf["rounds"] % traf["eval_every"] == 0
    assert (ROOT / "bench" / "checks" / f"{traf['check']}.py").is_file()
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


CONFIG_FILES = sorted((ROOT / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_file_loads_and_widths_match_the_model(path):
    from bench.references import lenet5

    conf = spec.load_json(path)
    assert conf["name"] == path.stem and NAME.match(conf["name"])
    assert all(k in conf["assumed"] or k in conf for k in conf["reduced"])
    d = lenet5.num_params(tuple(conf["image_hw"]), conf["channels"],
                          conf["num_classes"])
    assert conf["params"] == d
    assert conf["params_aligned"] == -(-d // 128) * 128


@pytest.mark.parametrize("path", sorted(
    (ROOT / "bench" / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_traffic_file_loads(path):
    traf = spec.load_json(path)
    assert traf["name"] == path.stem and NAME.match(traf["name"])
    assert traf["rounds"] % traf["eval_every"] == 0
    assert 1 <= traf["check_rounds"] <= traf["rounds"]
    assert {"strategy", "strategy_args", "participation", "check"} <= set(traf)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric, ROOT))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    assert LINE.match(entry["layer"])
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[entry["moves"]]
    for w in entry.get("workloads", WORKLOADS):
        assert "workloads" not in moved or w in moved["workloads"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", str(2**40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return not last.startswith("{")


def test_command_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "TPU" in proc.stderr


def test_command_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    env = {"PYTHONPATH": ""}
    proc = _run(tmp_path, env)
    assert proc.returncode != 0 and _no_result(proc)
    # past the look for a chip, run.py imports the harness, which needs
    # the program: without src/ that import fails before any result
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path[:0] = ['.', 'src']; "
         "import bench.harness"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "repro" in proc.stderr
