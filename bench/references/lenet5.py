"""Plain LeNet-5: parameters from a seed, forward pass, loss and accuracy.

Written from the paper's description (arXiv:2304.12930 Sec. V-A: LeNet-5,
two 5x5 valid convolutions with tanh and 2x2 average pooling, then three
dense layers with tanh between them), in straightforward jax.numpy. It
imports nothing of the code under test. The parameter dict uses the keys
and shapes the system under test takes as its model input.

``dtype`` selects the precision: float32 computes every convolution and
matrix product at ``Precision.HIGHEST``; bfloat16 stores and computes in
bfloat16, which is the control that has to fail the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(hw, channels, num_classes):
    """Leaf shapes by key; ``sorted(shapes)`` is JAX's flattening order."""
    h, w = hw
    flat = ((h - 4) // 2 - 4) // 2 * (((w - 4) // 2 - 4) // 2) * 16
    return {
        "c1_w": (5, 5, channels, 6), "c1_b": (6,),
        "c2_w": (5, 5, 6, 16), "c2_b": (16,),
        "f1_w": (flat, 120), "f1_b": (120,),
        "f2_w": (120, 84), "f2_b": (84,),
        "f3_w": (84, num_classes), "f3_b": (num_classes,),
    }


def num_params(hw, channels, num_classes):
    total = 0
    for shape in shapes(hw, channels, num_classes).values():
        size = 1
        for s in shape:
            size *= s
        total += size
    return total


def init(key, hw, channels, num_classes):
    """Glorot-uniform weights, zero biases, float32."""
    spec = shapes(hw, channels, num_classes)
    keys = dict(zip(("c1_w", "c2_w", "f1_w", "f2_w", "f3_w"),
                    jax.random.split(key, 5)))
    params = {}
    for name, shape in spec.items():
        if name.endswith("_b"):
            params[name] = jnp.zeros(shape, jnp.float32)
            continue
        fan_in = 1
        for s in shape[:-1]:
            fan_in *= s
        limit = (6.0 / (fan_in + shape[-1])) ** 0.5
        params[name] = jax.random.uniform(keys[name], shape, jnp.float32,
                                          -limit, limit)
    return params


def _conv(x, w, b, precision):
    """Valid 5x5 convolution (cross-correlation) as one matrix product of
    the image's patches with the kernel: rows of the reshaped HWIO kernel
    run over (row offset, column offset, input channel), as the patches'
    last axis does."""
    kh, kw, cin, cout = w.shape
    h, wd = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.concatenate([x[:, i:i + h, j:j + wd, :]
                               for i in range(kh) for j in range(kw)], -1)
    return jnp.dot(patches, w.reshape(kh * kw * cin, cout),
                   precision=precision) + b


def _pool(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def apply(params, x, dtype=jnp.float32):
    """x (batch, H, W, C) -> logits (batch, classes), computed in ``dtype``."""
    precision = HIGHEST if dtype == jnp.float32 else None
    p = {k: v.astype(dtype) for k, v in params.items()}
    y = jnp.tanh(_conv(x.astype(dtype), p["c1_w"], p["c1_b"], precision))
    y = _pool(y)
    y = jnp.tanh(_conv(y, p["c2_w"], p["c2_b"], precision))
    y = _pool(y)
    y = y.reshape(y.shape[0], -1)
    y = jnp.tanh(jnp.dot(y, p["f1_w"], precision=precision) + p["f1_b"])
    y = jnp.tanh(jnp.dot(y, p["f2_w"], precision=precision) + p["f2_b"])
    return jnp.dot(y, p["f3_w"], precision=precision) + p["f3_b"]


def loss(params, x, y, dtype=jnp.float32):
    """Mean cross-entropy of integer labels."""
    logits = apply(params, x, dtype).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def accuracy(params, x, y, dtype=jnp.float32):
    return jnp.mean((jnp.argmax(apply(params, x, dtype), -1) == y)
                    .astype(jnp.float32))
