"""Plain user-centric federated learning: Eq. 9 init, rounds, evaluation.

Written from arXiv:2304.12930 (Algorithm 1, Eqs. 8-10) in plain
jax.numpy over a dict of client-stacked LeNet-5 leaves, one block of
clients at a time. It imports nothing of the code under test and takes
nothing it made: the inputs are the benchmark's own data and weights, and
the randomness is re-derived from the same seeds by the same rules the
simulation documents:

* a trial key ``k`` splits into ``(k, init_key)``; round r splits ``k``
  into ``(k, round_key)``;
* round r's uniform cohort is ``numpy.random.default_rng(
  SeedSequence([pseed, r, 0x5EED])).choice(m, c, replace=False)``, sorted;
* client i's local key is ``split(round_key, m)[i]``; each epoch shuffles
  with ``permutation(epoch_key, n)`` and keeps whole batches;
* local SGD is heavy-ball momentum from a zero buffer every round:
  ``v = beta v + g``, ``theta -= lr v``;
* the PS mixes the cohort's uploads with W restricted to the cohort and
  row-renormalized (Eq. 8), and writes each member's row.

``dtype`` is the precision of the whole computation (float32 at
``Precision.HIGHEST``, or bfloat16 for the control). ``fault`` plants one
fault for the readings that set a limit's upper end: ``"half_batch"``
(every loss is the mean over the first half of its batch) or
``"misroute"`` (each round writes the second member's mixed model into the
first member's row as well).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.references import lenet5

HIGHEST = jax.lax.Precision.HIGHEST
COHORT_TAG = 0x5EED


def _loss(params, x, y, dtype, fault):
    if fault == "half_batch":
        half = x.shape[0] // 2
        x, y = x[:half], y[:half]
    return lenet5.loss(params, x, y, dtype)


def _blocks(fn, most, *args):
    """``fn`` vmapped over the leading (client) axis of every argument, a
    block of clients at a time (lax.map), so that it fits at any m. The
    block is the largest divisor of m up to ``most``: no padded copy of
    the data."""
    m = jax.tree.leaves(args)[0].shape[0]
    block = max(b for b in range(1, min(m, most) + 1) if m % b == 0)

    def prep(a):
        return a.reshape((m // block, block) + a.shape[1:])

    out = jax.lax.map(lambda xs: jax.vmap(fn)(*xs),
                      jax.tree.map(prep, args))
    return jax.tree.map(lambda a: a.reshape((m,) + a.shape[2:]), out)


def flatten(tree):
    """Client-stacked dict -> (m, d) in JAX's key order."""
    leaves = [tree[k] for k in sorted(tree)]
    m = leaves[0].shape[0]
    return jnp.concatenate([a.reshape(m, -1) for a in leaves], axis=1)


@functools.partial(jax.jit, static_argnames=("var_batch", "block", "dtype",
                                             "fault"))
def collaboration(params0, x, y, n, *, var_batch, block, dtype=jnp.float32,
                  fault=None):
    """The special round (Eqs. 9-10) from theta_0.

    Returns the per-client full gradients (a client-stacked dict), the
    Eq. 10 variances sigma^2 (m,) and the row-stochastic W (m, m)."""
    p0 = jax.tree.map(lambda a: a.astype(dtype), params0)
    grad = jax.grad(_loss)

    def one_client(xc, yc):
        k = xc.shape[0] // var_batch
        xb = xc[:k * var_batch].reshape((k, var_batch) + xc.shape[1:])
        yb = yc[:k * var_batch].reshape(k, var_batch)
        g = jax.lax.map(lambda ab: grad(p0, ab[0], ab[1], dtype, fault),
                        (xb, yb))
        full = jax.tree.map(lambda a: a.mean(axis=0), g)
        sq = sum(jnp.sum((a.astype(jnp.float32)
                          - f.astype(jnp.float32)[None]) ** 2,
                         axis=tuple(range(1, a.ndim)))
                 for a, f in zip(jax.tree.leaves(g), jax.tree.leaves(full)))
        return full, jnp.mean(sq)

    full, sigma_sq = _blocks(one_client, block, x, y)
    f = flatten(full)
    precision = HIGHEST if dtype == jnp.float32 else None
    gram = jnp.dot(f, f.T, precision=precision,
                   preferred_element_type=jnp.float32)
    sq = jnp.diag(gram)
    delta = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)
    return full, sigma_sq, mixing_weights(delta, sigma_sq, n)


def mixing_weights(delta, sigma_sq, n, eps=1e-12):
    """Eq. 9: w_ij proportional to (n_j / n_i) exp(-Delta_ij / (2 s_i s_j)),
    rows normalized; a zero sigma keeps only the zero-distance entries."""
    sig = jnp.sqrt(jnp.maximum(sigma_sq.astype(jnp.float32), 0.0))
    denom = 2.0 * sig[:, None] * sig[None, :]
    expo = jnp.where(denom > eps, -delta / jnp.maximum(denom, eps),
                     jnp.where(delta <= eps, 0.0, -jnp.inf))
    logn = jnp.log(n.astype(jnp.float32))
    logits = expo + logn[None, :] - logn[:, None]
    un = jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))
    return un / jnp.sum(un, axis=1, keepdims=True)


def cohort(pseed: int, rnd: int, m: int, c: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([pseed, rnd,
                                                        COHORT_TAG]))
    return np.sort(rng.choice(m, size=c, replace=False)).astype(np.int32)


def _local_sgd(params, x, y, key, *, lr, momentum, epochs, batch, dtype,
               fault):
    grad = jax.grad(_loss)
    n = x.shape[0]
    steps = n // batch
    for ekey in jax.random.split(key, epochs):
        perm = jax.random.permutation(ekey, n)[:steps * batch]
        xb = x[perm].reshape((steps, batch) + x.shape[1:])
        yb = y[perm].reshape(steps, batch)
        vel = jax.tree.map(jnp.zeros_like, params)

        def step(carry, b):
            p, v = carry
            g = grad(p, b[0], b[1], dtype, fault)
            v = jax.tree.map(lambda vi, gi: (momentum * vi + gi)
                             .astype(dtype), v, g)
            p = jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(dtype),
                             p, v)
            return (p, v), None

        (params, _), _ = jax.lax.scan(step, (params, vel), (xb, yb))
    return params


@functools.partial(jax.jit, static_argnames=("hp", "dtype", "fault"),
                   donate_argnums=(0,))
def _round(state, w, x, y, members, round_key, *, hp, dtype, fault):
    m = x.shape[0]
    keys = jax.random.split(round_key, m)[members]
    pre = jax.tree.map(lambda a: a[members], state)
    post = jax.vmap(functools.partial(_local_sgd, dtype=dtype, fault=fault,
                                      **dict(hp)))(
        pre, x[members], y[members], keys)
    rows = w[members][:, members]
    rows = rows / jnp.sum(rows, axis=1, keepdims=True)
    precision = HIGHEST if dtype == jnp.float32 else None
    mixed = jax.tree.map(
        lambda a: jnp.einsum("ij,j...->i...", rows.astype(dtype), a,
                             precision=precision).astype(dtype), post)
    if fault == "misroute":
        mixed = jax.tree.map(lambda a: a.at[0].set(a[1]), mixed)
    return jax.tree.map(lambda s, a: s.at[members].set(a), state, mixed)


def run(params0, data, key, pseed, *, rounds, cohort_size, lr, momentum,
        epochs, batch, var_batch, block, dtype=jnp.float32, fault=None):
    """One trial of ``rounds`` rounds; returns the collaboration statistics
    and the client-stacked final models (all device arrays)."""
    m = data["x"].shape[0]
    key, _ = jax.random.split(key)
    full, sigma_sq, w = collaboration(
        params0, data["x"], data["y"], data["n"], var_batch=var_batch,
        block=block, dtype=dtype, fault=fault)
    state = jax.tree.map(lambda a: jnp.broadcast_to(
        a.astype(dtype), (m,) + a.shape) + jnp.zeros((), dtype), params0)
    hp = (("lr", lr), ("momentum", momentum), ("epochs", epochs),
          ("batch", batch))
    for rnd in range(1, rounds + 1):
        key, rkey = jax.random.split(key)
        members = jnp.asarray(cohort(pseed, rnd, m, cohort_size))
        state = _round(state, w, data["x"], data["y"], members, rkey, hp=hp,
                       dtype=dtype, fault=fault)
    return {"full_grads": full, "sigma_sq": sigma_sq, "W": w,
            "params": state}


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def client_losses(params, x, y, *, block, dtype=jnp.float32):
    """(m,) mean cross-entropy of each client's model on its data."""
    return _blocks(lambda p, a, b: lenet5.loss(p, a, b, dtype), block,
                   params, x, y)


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def client_accuracies(params, x, y, *, block, dtype=jnp.float32):
    """(m,) test accuracy of each client's model."""
    return _blocks(lambda p, a, b: lenet5.accuracy(p, a, b, dtype), block,
                   params, x, y)
