"""The one generator of the benchmark's inputs: client data and weights.

A configuration file names the deployment (clients, samples per client,
image size, classes, and the kind of heterogeneity under ``data``); this
module turns it and a seed into device arrays in one jitted call. The
data is the repo's synthetic federated task (class-prototype images plus
noise, Dirichlet label mixes, rotation groups), copied here so that the
yardstick does not move when the program's own generator changes.

Seeds: every run derives its keys from ``--seed`` through
``numpy.random.SeedSequence``, which takes any non-negative integer, so
seeds past 2**32 stay distinct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.references import lenet5

DATA_TAG = 0xDA7A
PARAMS_TAG = 0x9A4A


def key_from(seed: int, *tags: int):
    """A raw uint32[2] PRNG key from a seed and tags."""
    words = np.random.SeedSequence([int(seed) % 2**64, *tags]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def trial_seeds(seed: int, index: int):
    """(jax key, participation seed) of trial ``index`` of a run."""
    words = np.random.SeedSequence([int(seed) % 2**64, 0x7121A1,
                                    index]).generate_state(3)
    return jnp.asarray(words[:2], jnp.uint32), int(words[2])


def _prototypes(key, num_classes, hw, channels, symmetric_frac=0.3):
    h, w = hw
    low = jax.random.normal(key, (num_classes, 7, 7, channels))
    proto = jax.image.resize(low, (num_classes, h, w, channels), "bicubic")
    proto = proto / (jnp.std(proto, axis=(1, 2, 3), keepdims=True) + 1e-6)
    n_sym = int(num_classes * symmetric_frac)
    if n_sym:
        sym = 0.5 * (proto[:n_sym] + jnp.rot90(proto[:n_sym], 2, axes=(1, 2)))
        proto = proto.at[:n_sym].set(sym)
    return proto


def _labels(key, m, n, num_classes, alpha):
    k1, k2 = jax.random.split(key)
    props = jax.random.dirichlet(k1, alpha * jnp.ones((num_classes,)), (m,))
    keys = jax.random.split(k2, m)
    return jax.vmap(lambda k, p: jax.random.choice(k, num_classes, (n,),
                                                   p=p))(keys, props)


def _rotate(x, r):
    """Rotate one client's images (n, H, W, C) by 90 degrees times r."""
    out = x
    for k in range(1, 4):
        out = jnp.where(r == k, jnp.rot90(x, k, axes=(1, 2)), out)
    return out


def _images(key, proto, labels, group, noise, most=64):
    """Images of every client, a block of clients at a time (the largest
    divisor of m up to ``most``), so that the device holds the result and
    one block's temporaries, not several copies of the whole data."""
    m = labels.shape[0]
    block = max(b for b in range(1, min(m, most) + 1) if m % b == 0)
    keys = jax.random.split(key, m)

    def one(k, y, g):
        eps = jax.random.normal(k, y.shape + proto.shape[1:])
        return _rotate(proto[y] + noise * eps, g)

    def prep(a):
        return a.reshape((m // block, block) + a.shape[1:])

    out = jax.lax.map(lambda xs: jax.vmap(one)(*xs),
                      (prep(keys), prep(labels), prep(group)))
    return out.reshape((m,) + out.shape[2:])


def _generate(key, *, m, n, n_test, hw, channels, num_classes, scenario,
              alpha, noise, groups):
    kp, kl, kx, klt, kxt = jax.random.split(key, 5)
    proto = _prototypes(kp, num_classes, hw, channels)
    y = _labels(kl, m, n, num_classes, alpha).astype(jnp.int32)
    y_test = _labels(klt, m, n_test, num_classes, alpha).astype(jnp.int32)
    if scenario == "covariate_label_shift":
        group = jnp.arange(m, dtype=jnp.int32) % groups
    elif scenario == "label_shift":
        group = jnp.zeros((m,), jnp.int32)
    else:
        raise ValueError(f"unknown data scenario {scenario!r}")
    return {"x": _images(kx, proto, y, group, noise), "y": y,
            "x_test": _images(kxt, proto, y_test, group, noise),
            "y_test": y_test, "group": group,
            "n": jnp.full((m,), n, jnp.int32)}


def _inputs(dkey, pkey, **kw):
    data = _generate(dkey, **kw)
    params0 = lenet5.init(pkey, kw["hw"], kw["channels"], kw["num_classes"])
    return data, params0


def generate(config: dict, seed: int):
    """(data dict, params0 dict) on the default device, from ``seed``."""
    spec = config["data"]
    fn = jax.jit(functools.partial(
        _inputs, m=config["clients"], n=config["train_per_client"],
        n_test=config["test_per_client"], hw=tuple(config["image_hw"]),
        channels=config["channels"], num_classes=config["num_classes"],
        scenario=spec["scenario"], alpha=float(spec["alpha"]),
        noise=float(spec["noise"]), groups=int(spec.get("groups", 1))))
    return fn(key_from(seed, DATA_TAG), key_from(seed, PARAMS_TAG))
