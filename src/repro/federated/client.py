"""Client-side local optimization (vmapped across the client axis).

``make_local_sgd`` builds the paper's ClientUpdate procedure: E epochs of
minibatch SGD (η=0.1, β=0.9 heavy-ball momentum, fresh optimizer each
round), as a jit/scan program. A ``grad_hook`` lets baselines inject
per-step gradient corrections (FedProx proximal term, SCAFFOLD control
variates, Ditto/pFedMe regularizers) without duplicating the loop.

Memory knob: ``make_federated_local_sgd(..., chunk_size=C)`` replaces the
monolithic client vmap with a sequential loop over ⌈m/C⌉ chunks of
C clients each, so peak activation memory is O(C) instead of O(m) while
per-client results stay identical (same per-client PRNG keys). Use it to
scale the client axis (or a sampled cohort) to thousands of clients on a
single host; leave it ``None`` for the fastest fully-parallel path.

Parallel knob: ``make_federated_local_sgd(..., mesh=...)`` shards the
client/slot axis across a 1-D device mesh (see
:mod:`repro.federated.mesh`): each device runs the vmapped local SGD on
its own block of rows under ``shard_map`` and the per-row results are
all-gathered back, so cohort wall-time scales down with the shard count
instead of growing linearly with cohort size. ``chunk_size`` composes —
chunking applies *within* each device's shard. Results match
``mesh=None`` within float32 round-off (see
:func:`repro.federated.mesh.shard_clients` for why not bit-exact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.data.loader import epoch_batches
from repro.federated import mesh as mesh_lib
from repro.federated import spans
from repro.optim import sgd_init, sgd_update


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def make_loss(apply_fn):
    def loss(params, x, y):
        return cross_entropy(apply_fn(params, x), y)
    return loss


def make_local_sgd(apply_fn, *, lr=0.1, momentum=0.9, epochs=1,
                   batch_size=50, grad_hook=None):
    """Returns local_sgd(params, x, y, key, hook_state) -> (params, hook_state).

    hook_state is an arbitrary pytree threaded through every SGD step and
    passed to ``grad_hook(grads, params, hook_state) -> (grads, hook_state)``.
    """
    loss = make_loss(apply_fn)
    grad_fn = jax.grad(loss)

    def local_sgd(params, x, y, key, hook_state=None):
        def one_epoch(carry, ekey):
            params, mom, hstate = carry
            xb, yb = epoch_batches(ekey, x, y, batch_size)

            def step(c, batch):
                params, mom, hstate = c
                bx, by = batch
                g = grad_fn(params, bx, by)
                if grad_hook is not None:
                    g, hstate = grad_hook(g, params, hstate)
                params, mom = sgd_update(g, mom, params, lr=lr,
                                         momentum=momentum)
                return (params, mom, hstate), None

            (params, mom, hstate), _ = jax.lax.scan(
                step, (params, mom, hstate), (xb, yb)
            )
            return (params, mom, hstate), None

        mom = sgd_init(params, momentum=momentum)
        (params, _, hook_state), _ = jax.lax.scan(
            one_epoch, (params, mom, hook_state), jax.random.split(key, epochs)
        )
        return params, hook_state

    return local_sgd


def client_vmap(fn, *, chunk_size=None, mesh=None):
    """vmap ``fn`` over a shared leading client axis of every argument.

    With ``chunk_size=C`` the client axis is instead processed as a
    sequential loop over chunks of C vmapped clients (last chunk padded
    by repeating index 0; padding results are discarded), each chunk's
    rows taken inside the loop, bounding peak memory by the chunk instead
    of the full axis while keeping per-client results identical to the
    monolithic vmap. Arguments that are ``None`` (empty pytrees) pass
    through unmapped.

    With ``mesh`` (a :mod:`repro.federated.mesh` knob: Mesh | int |
    ``"auto"``) the client axis is partitioned across the mesh's devices
    under ``shard_map``: each device runs the chunked vmap on its own
    block and the per-row results are all-gathered back to full
    replicated arrays (matching the unsharded vmap within f32 round-off;
    see :func:`repro.federated.mesh.shard_clients`). Chunking applies
    *within* each shard. An axis not divisible by the shard count falls
    back to the unsharded path — the cohort engine pads slot counts to a shard
    multiple (:func:`repro.federated.mesh.pad_cohort`) so the masked
    round always shards; a dense m that the mesh doesn't divide simply
    stays single-device.
    """
    mesh = mesh_lib.resolve(mesh)
    vfn = jax.vmap(fn)

    def block(args):
        m = jax.tree.leaves(args)[0].shape[0]
        if chunk_size is None or m <= chunk_size:
            return vfn(*args)

        nc = -(-m // chunk_size)
        pad = nc * chunk_size - m

        # each chunk's rows are taken inside the loop and its results
        # written in place into the (m, ...) output: padding the whole
        # axis would copy every input, and reshaping stacked (nc, C, ...)
        # results copies all of them again where C is not a multiple of
        # the device layout's 8-row tile
        def rows(i):
            start = i * chunk_size
            if not pad:
                return lambda a: jax.lax.dynamic_slice_in_dim(
                    a, start, chunk_size)
            # the padded last chunk repeats row 0; its results are dropped
            idx = start + jnp.arange(chunk_size)
            idx = jnp.where(idx < m, idx, 0)
            return lambda a: jnp.take(a, idx, axis=0)

        def body(i, out):
            res = vfn(*jax.tree.map(rows(i), args))
            return jax.tree.map(
                lambda o, r: jax.lax.dynamic_update_slice_in_dim(
                    o, r, i * chunk_size, 0), out, res)

        shapes = jax.eval_shape(
            lambda *a: vfn(*a), *jax.tree.map(lambda a: a[:chunk_size], args))
        out = jax.tree.map(lambda s: jnp.zeros((nc * chunk_size,)
                                               + s.shape[1:], s.dtype),
                           shapes)
        out = jax.lax.fori_loop(0, nc, body, out)
        return jax.tree.map(lambda a: a[:m], out) if pad else out

    def mapped(*args):
        m = jax.tree.leaves(args)[0].shape[0]
        if mesh is not None and m % mesh_lib.num_shards(mesh) == 0:
            return mesh_lib.shard_clients(
                lambda *local_args: block(local_args), mesh)(*args)
        return block(args)

    return mapped


def make_federated_local_sgd(apply_fn, *, chunk_size=None, mesh=None, **kw):
    """:func:`client_vmap` of ``make_local_sgd`` over the client axis.

    Returns fed(stacked_params, x, y, key, hook_state) -> (params, hook_state);
    hook_state leaves, when present, must carry a leading client axis.
    ``chunk_size`` bounds peak memory and ``mesh`` shards the client axis
    across devices (see :func:`client_vmap`).
    """
    local = make_local_sgd(apply_fn, **kw)
    run = client_vmap(local, chunk_size=chunk_size, mesh=mesh)

    def fed(stacked_params, x, y, key, hook_state=None, *, keys=None):
        # ``keys`` overrides the default split(key, m) per-row derivation
        # with precomputed per-row keys — the masked cohort engine passes
        # client-indexed keys so a slot's randomness is independent of the
        # cohort's slot count (padding invariance).
        with spans.scope(spans.LOCAL_SGD):
            if keys is None:
                keys = jax.random.split(key, x.shape[0])
            return run(stacked_params, x, y, keys, hook_state)

    return fed


def full_gradients(apply_fn, stacked_params, x, y):
    """Per-client full-batch gradients (the special round's upload)."""
    loss = make_loss(apply_fn)
    return jax.vmap(jax.grad(loss))(stacked_params, x, y)


def minibatch_gradients(apply_fn, stacked_params, xb, yb):
    """Gradients on a fixed minibatch partition: xb (m, K, B, ...)."""
    loss = make_loss(apply_fn)
    g = jax.vmap(jax.vmap(jax.grad(loss), in_axes=(None, 0, 0)))(
        stacked_params, xb, yb
    )
    return g  # leaves: (m, K, ...)


def evaluate(apply_fn, stacked_params, x_test, y_test, *, batch=None,
             mesh=None):
    """Per-client test accuracy. Returns (m,) accuracies.

    ``batch`` bounds the client axis via :func:`client_vmap`'s
    ``chunk_size`` path: accuracies are computed as a sequential
    loop over chunks of that many clients, so peak activation
    memory is O(batch · test_set) instead of O(m · test_set). ``None``
    keeps the fully-parallel vmap (identical results). ``mesh`` shards
    the client axis across devices; logits then match the unsharded
    pass only within f32 round-off (see :func:`client_vmap`), so a
    near-tied argmax can in principle flip a prediction.
    """

    def acc_one(params, x, y):
        logits = apply_fn(params, x)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    return client_vmap(acc_one, chunk_size=batch, mesh=mesh)(
        stacked_params, x_test, y_test)
