"""The compiled Eq. 9 special round (``ucfl.compute_collaboration``).

The per-client gradient pass with σ², the Gram and Δ with W run as a few
compiled launches: they give what the same maths gives op by op, and a
strategy's second init on the same data compiles nothing."""
import functools

import jax
import numpy as np
import pytest

from repro.core import FedConfig, ucfl
from repro.data import synthetic
from repro.kernels import ops
from repro.models import lenet

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@functools.lru_cache(maxsize=1)
def _setup():
    key = jax.random.PRNGKey(23)
    dkey, mkey = jax.random.split(key)
    # m = 7 leaves the last chunk of 3 padded
    data = synthetic.label_shift(dkey, m=7, n=60, n_test=10, num_classes=5,
                                 hw=(16, 16), channels=1)
    params0 = lenet.init(mkey, input_hw=(16, 16), channels=1, num_classes=5)
    return data, params0


@pytest.mark.parametrize("chunk_size", [None, 3])
def test_compiled_collaboration_matches_eager(chunk_size):
    data, params0 = _setup()
    run = functools.partial(ucfl.compute_collaboration, lenet.apply, params0,
                            data, var_batch_size=20, chunk_size=chunk_size)
    compiled = run()
    with jax.disable_jit():
        eager = run()
        # Δ and W of the same gradients, op by op
        gram = ops.gram(compiled["full_grads"])
        delta, w = ucfl._delta_and_weights(gram, compiled["sigma_sq"], data.n)
    np.testing.assert_array_equal(np.asarray(compiled["delta"]),
                                  np.asarray(delta))
    np.testing.assert_array_equal(np.asarray(compiled["W"]), np.asarray(w))
    for key in ("full_grads", "sigma_sq", "W"):
        np.testing.assert_allclose(np.asarray(compiled[key]),
                                   np.asarray(eager[key]),
                                   rtol=1e-5, atol=1e-6, err_msg=key)
    # G_ii + G_jj - 2 G_ij cancels: gradients a few ulps apart (another
    # reduction order) give Δs a few ulps of the largest Gram entry apart
    ulp = np.finfo(np.float32).eps * float(np.max(np.asarray(gram)))
    np.testing.assert_allclose(np.asarray(compiled["delta"]),
                               np.asarray(eager["delta"]),
                               rtol=1e-5, atol=8 * ulp)


def test_second_init_compiles_nothing():
    data, params0 = _setup()
    cfg = FedConfig(lr=0.1, momentum=0.9, epochs=1, batch_size=20)
    strategy = ucfl.make_ucfl(lenet.apply, params0, cfg, var_batch_size=20)
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(strategy.init(key, data))
    compiled = []

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compiled.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        jax.block_until_ready(strategy.init(key, data))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiled == []


def test_init_chunk_is_derived_from_the_data():
    """Without a chunk the paper cell's 20 clients of 10 Eq. 10
    minibatches of 50 run at once; FEMNIST's 3,550 writers of 4 run in 71
    chunks of 50 (10,000 samples each, the paper's whole batch); a prime
    count takes the budget's chunk with a padded last one; an explicit
    ``chunk_size`` wins."""
    assert ucfl.init_chunk(20, 500, 50) == 20
    assert ucfl.init_chunk(3550, 204, 50) == 50
    assert ucfl.init_chunk(101, 204, 50) == 50
    assert ucfl.init_chunk(3550, 204, 50, chunk_size=5) == 5
    assert ucfl.init_chunk(20, 500, 50, chunk_size=3) == 3


def test_collaboration_runs_the_derived_chunk(monkeypatch):
    """compute_collaboration hands the gradient program the derived chunk
    (here 3 of 7 clients, a padded last chunk), and the chunked pass gives
    the unchunked gradients."""
    data, params0 = _setup()
    run = functools.partial(ucfl.compute_collaboration, lenet.apply, params0,
                            data, var_batch_size=20)
    whole = run()
    monkeypatch.setattr(ucfl, "INIT_CHUNK_SAMPLES", 3 * 60)
    assert ucfl.init_chunk(7, 60, 20) == 3
    seen = []
    inner = ucfl._special_round_uploads

    def spy(*args, **kwargs):
        seen.append(kwargs["chunk_size"])
        return inner(*args, **kwargs)

    monkeypatch.setattr(ucfl, "_special_round_uploads", spy)
    chunked = run()
    assert seen == [3]
    for key in ("full_grads", "sigma_sq", "W"):
        np.testing.assert_allclose(np.asarray(chunked[key]),
                                   np.asarray(whole[key]),
                                   rtol=1e-5, atol=1e-6, err_msg=key)
