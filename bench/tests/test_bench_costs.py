"""The operation and byte counts the roofline and mfu metrics divide by:
against hand counts, and against XLA's cost analysis on the CPU."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.costs import gram, lenet, mix_scatter  # noqa: E402
from repro.models import lenet as model  # noqa: E402


def _cost(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return cost[0] if isinstance(cost, list) else cost


def test_lenet_hand_count_28x28_20_classes():
    # conv1 24*24*6*25, conv2 8*8*16*150, dense 256*120 + 120*84 + 84*20
    first = 86_400
    rest = 153_600 + 30_720 + 10_080 + 1_680
    assert lenet.forward_flops((28, 28), 1, 20) == 2 * (first + rest)
    assert lenet.train_flops((28, 28), 1, 20) == 2 * (2 * first + 3 * rest)


@pytest.mark.parametrize("hw,classes", [((28, 28), 20), ((28, 28), 62),
                                        ((16, 16), 8)])
def test_lenet_counts_against_xla(hw, classes):
    """On the model the program runs: XLA also counts activations,
    pooling, biases and the loss, so it reads a few percent above the
    multiply-adds, never below."""
    batch = 4
    params = model.init(jax.random.PRNGKey(0), input_hw=hw, channels=1,
                        num_classes=classes)
    x = jnp.zeros((batch,) + hw + (1,))
    y = jnp.zeros((batch,), jnp.int32)

    def loss(p, x, y):
        logp = jax.nn.log_softmax(model.apply(p, x))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    fwd = _cost(model.apply, params, x)["flops"] / batch
    train = _cost(jax.grad(loss), params, x, y)["flops"] / batch
    ours_fwd = lenet.forward_flops(hw, 1, classes)
    ours_train = lenet.train_flops(hw, 1, classes)
    assert 0.9 * fwd <= ours_fwd <= fwd
    assert 0.9 * train <= ours_train <= train


def test_gram_counts():
    m, d = 16, 1024
    assert gram.flops(m, d) == 2 * m * m * d
    assert gram.hbm_bytes(m, d) == 4 * (m * d + m * m)
    cost = _cost(lambda g: g @ g.T, jnp.zeros((m, d)))
    assert cost["flops"] == gram.flops(m, d)
    # XLA reads the one operand twice (as g and as g.T); the kernel once
    assert cost["bytes accessed"] == gram.hbm_bytes(m, d) + 4 * m * d


def test_mix_scatter_counts():
    m, c, d = 40, 8, 1024
    # state in and out, uploads, rows, int32 indices and mask
    assert mix_scatter.hbm_bytes(m, c, d) == (4 * (2 * m * d + c * d + c * c)
                                              + 8 * c)
    assert mix_scatter.flops(m, c, d) == 2 * c * c * d
    cost = _cost(lambda w, t: w @ t, jnp.zeros((c, c)), jnp.zeros((c, d)))
    assert cost["flops"] == mix_scatter.flops(m, c, d)
