"""Device mesh for sharding the cohort/client axis across devices.

``chunk_size`` bounds single-host memory by running local SGD as a
*sequential* loop over chunks — cohort wall-time grows linearly
with cohort size even when devices sit idle. This module adds the
*parallel* scale axis: a 1-D :class:`jax.sharding.Mesh` over a
``clients`` axis partitions the padded cohort slots across devices, so
a cohort of c slots runs local SGD as ``num_shards`` concurrent blocks
of ``c / num_shards`` slots (each block still chunked by ``chunk_size``
*within* its shard — the two knobs compose).

Three arrays ride the cohort axis and share one sharding
(:func:`slot_sharding`): the padded ``Cohort(indices, mask)`` slot
arrays, the per-slot client-indexed PRNG key batch, and the raveled
(c, d) update slab. The (m, d) stacked state and the (c, c) per-slot
mix rules stay replicated: the per-slot updates are all-gathered right
after local SGD (inside :func:`shard_clients`, used by
``repro.federated.client.client_vmap``) and the mix + fused
``masked_mix_scatter`` then run identically on every device's
host-local copy of the (m, d) state — the mix is tiny next to local
SGD, and keeping it replicated preserves the donation/aliasing story of
the unsharded engine unchanged.

Shape contract: ``shard_map`` requires the slot count to divide evenly
across shards, so :func:`pad_cohort` rounds every cohort up to the next
multiple of ``num_shards(mesh)`` with sentinel slots (index m, mask
False) — the exact padding the fixed-shape engine already treats as
bit-invisible (zero weight in every masked rule, dropped by the
scatter, client-indexed PRNG keys). A policy's slot count is static, so
the padded count is static too and the one-compilation guarantee
survives under a fixed mesh.

Running multi-device on CPU (no accelerator required)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    JAX_PLATFORMS=cpu python ...

forces 8 host devices (set *before* the first jax import);
``FedConfig(mesh=8)`` — or ``mesh="auto"`` for all local devices — then
shards every cohort round 8 ways. This is how CI exercises the mesh
code path on every PR (the ``multi-device`` job).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.federated import participation

AXIS = "clients"


# Every shard_map below passes check_vma=False: the all-gathered outputs
# are replicated, but the static replication checker cannot infer that
# through lax.all_gather.


def client_mesh(num_shards: int | None = None, *, devices=None):
    """Build the 1-D ``clients`` mesh over the first ``num_shards`` devices."""
    if devices is None:
        devices = jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    if not 1 <= int(num_shards) <= len(devices):
        raise ValueError(
            f"need 1 <= num_shards <= {len(devices)} local devices, "
            f"got {num_shards}")
    return jax.sharding.Mesh(np.asarray(devices[:int(num_shards)]), (AXIS,))


def resolve(mesh):
    """Normalize the ``FedConfig.mesh`` knob to a Mesh (or None).

    Accepts ``None`` (sharding off), a 1-D :class:`jax.sharding.Mesh`
    whose single axis enumerates clients, an int shard count, or
    ``"auto"`` (all local devices).
    """
    if mesh is None:
        return None
    if isinstance(mesh, jax.sharding.Mesh):
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"client mesh must be 1-D, got axes {mesh.axis_names}")
        return mesh
    if mesh == "auto":
        return client_mesh()
    return client_mesh(int(mesh))


def num_shards(mesh) -> int:
    return int(mesh.devices.size)


def _axis(mesh) -> str:
    return mesh.axis_names[0]


def slot_sharding(mesh) -> NamedSharding:
    """Sharding of every per-slot array: cohort ``indices``/``mask``, the
    per-slot key batch, and the raveled (c, d) update slab — axis 0
    partitioned across the mesh."""
    return NamedSharding(mesh, P(_axis(mesh)))


def replicated_sharding(mesh) -> NamedSharding:
    """Sharding of the (m, d) stacked state and the (c, c) mix rules."""
    return NamedSharding(mesh, P())


def replicated(fn, mesh):
    """Run ``fn`` whole on every device of ``mesh``, each on its own
    replicated copy of every argument.

    A Pallas TPU (Mosaic) kernel cannot be partitioned automatically:
    lowered in a jit that spans several devices it raises unless it sits
    inside a shard_map. Server-side math of the replicated layout (the
    fused mix-scatter, the Eq. 9 Gram matrix, K-means) goes through here,
    so each device runs the kernel on its full copy — what the replicated
    layout computes anyway.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def pad_to_shards(slots: int, shards: int) -> int:
    """Round a slot count up to the next multiple of the shard count."""
    return -(-int(slots) // int(shards)) * int(shards)


def pad_cohort(cohort: participation.Cohort, mesh,
               m: int) -> participation.Cohort:
    """Pad a cohort's slot count to a multiple of the mesh's shard count.

    The extra slots are sentinel pads (index ``m``, mask False) — bit-
    invisible to the masked engine. No-op when already divisible (in
    particular for a 1-device mesh).
    """
    return participation.pad_slots(
        cohort, pad_to_shards(cohort.num_slots, num_shards(mesh)), m)


def commit_replicated(tree, mesh):
    """Commit every ``jax.Array`` leaf of ``tree`` to the replicated
    sharding of ``mesh``.

    The sharded round's outputs are replicated over the mesh, so from
    round 2 on the state enters the jitted round replicated-committed.
    Committing the *initial* state the same way keeps every call's input
    shardings identical — without this, the steady-state input sharding
    first appears on round 2 and jit compiles the round a second time
    inside the timed region (the cohort dispatcher calls this; it is a
    copy-free no-op once the state is already committed). Host (numpy)
    leaves — e.g. CFL's cluster bookkeeping — are untouched.
    """
    sh = replicated_sharding(mesh)
    return jax.tree.map(
        lambda x: jax.device_put(x, sh) if isinstance(x, jax.Array) else x,
        tree)


# ------------------------------------------------------- row-sharded state
#
# The replicated engine keeps the whole (m, d) stacked state on every
# device. The row-sharded layout partitions the LEADING (client) axis of
# every state leaf across the ``clients`` mesh instead — device k owns
# rows [k·m/s, (k+1)·m/s) — so server memory AND per-round bandwidth
# scale down with the device count. Every cohort row is routed to its
# owner shard inside a shard_map body: ownership of slot i on device k
# is ``lo <= idx[i] < lo + m/s`` (lo = k·m/s); non-owned slots are
# localized to the per-block sentinel m/s, which the sentinel-drop
# scatter contract already treats as a pad. The cohort gather is a
# (c, d) psum of one-hot-owned rows and the scatter/mix write only the
# owner block, so the only model-sized collectives are O(c·d) — never
# O(m·d). Opt in via ``FedConfig.shard_state`` (requires a mesh);
# ``mesh=None`` and the replicated layout stay bit-exact.


def row_sharding(mesh) -> NamedSharding:
    """Sharding of a row-sharded (m, ·) state leaf: leading axis
    partitioned across the ``clients`` mesh."""
    return NamedSharding(mesh, P(_axis(mesh)))


def commit_rows(tree, mesh):
    """Commit every ``jax.Array`` leaf of ``tree`` to the row sharding.

    The row-sharded round's state outputs carry this sharding already
    (shard_map out_specs), so — exactly like :func:`commit_replicated` —
    this is a copy-free no-op from round 2 on; committing the initial
    state keeps every call's input shardings identical and preserves the
    one-compilation guarantee. Host (numpy) leaves are untouched.
    """
    s = num_shards(mesh)
    sh = row_sharding(mesh)

    def put(x):
        if not isinstance(x, jax.Array):
            return x
        if x.shape[0] % s:
            raise ValueError(
                f"row-sharded state needs a leading axis divisible by the "
                f"{s}-device mesh, got shape {x.shape} (pad m to a shard "
                f"multiple or drop FedConfig.shard_state)")
        return jax.device_put(x, sh)

    return jax.tree.map(put, tree)


def constrain_rows(tree, mesh):
    """Pin a traced (m, ·) tree to the row sharding inside jit.

    Used where a strategy's state output is produced by plain jnp ops
    (e.g. SCAFFOLD's broadcast server control) rather than a shard_map —
    without the constraint the round's output sharding could differ from
    the committed input sharding and trigger a recompile on round 2.
    """
    sh = row_sharding(mesh)
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, sh), tree)


def _localize(idx, mb: int, axis: str):
    """Map global row ids to block-local ids on the current shard.

    Returns ``(loc, own)``: ``own`` marks the slots this shard owns and
    ``loc`` is their block-local row (non-owned slots — including the
    global sentinel m — get the local sentinel mb, dropped by every
    ``mode="drop"`` scatter).
    """
    lo = jax.lax.axis_index(axis) * mb
    own = (idx >= lo) & (idx < lo + mb)
    return jnp.where(own, idx - lo, mb).astype(idx.dtype), own


def shard_gather_rows(tree, safe, mesh):
    """Cohort gather from a row-sharded state: each device contributes
    the rows it owns (zeros elsewhere) and a (c, d)-sized psum assembles
    the replicated cohort — O(c·d) traffic, never O(m·d). ``safe`` must
    be pre-clamped (``aggregation.safe_gather_index``), matching the
    replicated ``jnp.take`` semantics exactly."""
    axis = _axis(mesh)

    def body(block, safe):
        mb = jax.tree.leaves(block)[0].shape[0]
        lo = jax.lax.axis_index(axis) * mb
        own = (safe >= lo) & (safe < lo + mb)
        loc = jnp.clip(safe - lo, 0, mb - 1)
        part = jax.tree.map(
            lambda b: jnp.where(
                own.reshape((-1,) + (1,) * (b.ndim - 1)),
                jnp.take(b, loc, axis=0), 0), block)
        return jax.tree.map(lambda x: jax.lax.psum(x, axis), part)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)(tree, safe)


def shard_scatter_rows(tree, idx, updates, mesh):
    """Cohort scatter into a row-sharded state: each device writes only
    the rows it owns (localized indices; non-owned and pad slots drop on
    the local sentinel). No collective at all — the (c, d) updates are
    already replicated."""
    axis = _axis(mesh)

    def body(block, idx, updates):
        mb = jax.tree.leaves(block)[0].shape[0]
        loc, _ = _localize(idx, mb, axis)
        return jax.tree.map(
            lambda b, u: b.at[loc].set(u.astype(b.dtype), mode="drop"),
            block, updates)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(), P()),
                         out_specs=P(axis), check_vma=False)(tree, idx,
                                                             updates)


def shard_block_update(fn, mesh, *, gather_args=0):
    """Run a masked row-rewrite on each shard's block of the state.

    Builds ``update(tree, idx, mask, *args) -> tree'`` where ``fn(block,
    loc_idx, loc_mask, *args)`` rewrites one device's (m/s, ·) row block;
    ``idx``/``mask`` are localized per shard (non-owned slots get the
    local sentinel / a False mask, so the fused masked kernels and
    ``mode="drop"`` scatters apply unchanged per block) and ``*args``
    stay replicated — except the first ``gather_args`` of them, which
    enter ROW-SHARDED and are all-gathered (tiled) inside the body
    before ``fn`` sees them (the buffered-async flush passes its sharded
    (B, d) pending-upload shard this way: that gather is the flush's one
    model-sized collective).
    """
    axis = _axis(mesh)

    def update(tree, idx, mask, *args):
        def body(block, idx, mask, *args):
            mb = jax.tree.leaves(block)[0].shape[0]
            loc, own = _localize(idx, mb, axis)
            args = tuple(
                jax.lax.all_gather(a, axis, axis=0, tiled=True)
                if i < gather_args else a
                for i, a in enumerate(args))
            return fn(block, loc, mask & own, *args)

        specs = tuple(P(axis) if i < gather_args else P()
                      for i in range(len(args)))
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(axis), P(), P()) + specs,
                             out_specs=P(axis), check_vma=False)(
                                 tree, idx, mask, *args)

    return update


def shard_broadcast_rows(full, mixed, alive, mesh):
    """FedAvg-family broadcast into a row-sharded state: every device
    rewrites its block with the replicated (1, ·) mix; ``alive`` False
    (an all-masked cohort) keeps the previous block instead."""
    axis = _axis(mesh)

    def body(block, mixed, alive):
        return jax.tree.map(
            lambda x, p: jnp.where(
                alive, jnp.broadcast_to(x, (p.shape[0],) + x.shape[1:]), p),
            mixed, block)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(), P()),
                         out_specs=P(axis), check_vma=False)(full, mixed,
                                                             alive)


def all_gather_rows(x, mesh):
    """Replicate a row-sharded array: tiled all_gather over the leading
    axis (the buffered-async flush's one model-sized collective)."""
    axis = _axis(mesh)
    return jax.shard_map(
        lambda b: jax.lax.all_gather(b, axis, axis=0, tiled=True),
        mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)(x)


def shard_clients(fn, mesh):
    """shard_map ``fn`` over the leading client/slot axis of every arg.

    Each device receives its contiguous block of rows and runs ``fn`` on
    it; the per-row outputs are all-gathered (tiled) back to full
    arrays, so callers downstream — the (c, c) mix, the fused scatter —
    see replicated values and need no sharding awareness. This is the
    "mix after an all-gather of the (c, d) updates" step of the sharded
    round. Row order is preserved and per-row computation is
    semantically identical to the unsharded vmap; numerically, results
    match ``mesh=None`` within float32 round-off (XLA picks reduction
    tilings per *local* batch shape, so convolution/matmul reductions
    inside a row can associate differently — observed ulp-level only;
    sentinel-slot padding itself is bit-exact).
    """
    axis = _axis(mesh)

    def body(*args):
        out = fn(*args)
        return jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True), out)

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(),
                         check_vma=False)
