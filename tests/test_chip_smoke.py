"""chip_smoke.py's phases at a tiny size on CPU (Pallas interpret mode),
its refusals off the chip, and the compile-cache directory choice."""
import os
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from benchmarks.common import BenchScale  # noqa: E402
from repro import compile_cache  # noqa: E402

TINY = BenchScale(m=4, n=40, n_test=10, num_classes=4, hw=(16, 16),
                  groups=2, batch_size=20, var_batch=20)


@pytest.fixture
def restore_cache_dir():
    """main() and configure() point JAX's cache at a directory; put the
    setting back so later tests in this worker compile without it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("scenario,strategy", [
    ("label_shift", "ucfl"), ("covariate_label_shift", "ucfl_k2")])
def test_round_phase_interpret_matches_ref(scenario, strategy):
    got = chip_smoke.run_phase(scenario, strategy, TINY, impl="interpret",
                               rounds=2, cohort=2)
    want = chip_smoke.run_phase(scenario, strategy, TINY, impl="ref",
                                rounds=2, cohort=2)
    diff = chip_smoke.compare(got, want)
    chip_smoke.check_close(strategy, diff, got)
    assert diff["max_abs_diff"] < 1e-4
    assert got["round_fn"] == "_masked"
    assert got["params"].shape[0] == TINY.m
    assert got["compile_s"] > 0 and got["round_s"] > 0


@pytest.mark.parametrize("variant", ["hbm", "slab"])
def test_rows_phase_interpret(variant):
    res = chip_smoke.phase_rows(24, 5, 300, impl=f"interpret_{variant}")
    assert res["gather_max_abs_diff"] == 0.0
    assert res["mix_scatter_max_abs_diff"] <= 1e-5


def test_slab_bound_is_the_last_fitting_row_count():
    from repro.kernels.masked_mix_scatter import slab_fits

    m = chip_smoke.slab_bound(64)
    assert m % 8 == 0 and slab_fits(m, 64) and not slab_fits(m + 8, 64)


def test_main_refuses_without_tpu(capsys, monkeypatch, restore_cache_dir):
    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "TPU" in out.err


def test_main_refuses_non_pallas_impl(capsys, monkeypatch,
                                      restore_cache_dir):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "REPRO_KERNEL_IMPL" in out.err


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    assert os.path.basename(path) == ".jax_cache"
