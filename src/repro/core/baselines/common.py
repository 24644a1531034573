"""Helpers shared by the baseline strategies — including the single
cohort-dispatch engine every strategy's ``round`` is built from.

PR 1 gave each of the eleven strategies its own hand-written
``round(state, data, key, cohort)`` wrapper repeating the same
``if cohort is None: dense else gather/train/mix/scatter`` shape, and the
availability sampler re-jitted the cohort path on every distinct
eligible-set size. The engine here replaces all of that:

  * :func:`cohort_round` — the ONE dispatch point. Normalizes the cohort
    argument to the padded ``(indices, mask)`` contract
    (:func:`repro.federated.participation.as_cohort`), routes to the
    dense or masked jitted path, and attaches the host-side
    ``cohort_size`` metric. Because every padded cohort of a policy has
    the same slot count, the masked path compiles exactly once.
  * :func:`make_masked_round` — the standard masked round body
    (masked gather -> chunked local SGD -> masked mix -> fused scatter)
    jitted with ``donate_argnums=(0,)`` so the (m, d) stacked-params
    buffer is updated in place instead of paying a full HBM copy per
    round. Strategies with extra stacked state (SCAFFOLD controls, Ditto
    / pFedMe personal models) keep custom jitted bodies but reuse the
    same pieces.

Slab state layout
-----------------
Every strategy's stacked device state — ``params``, SCAFFOLD controls,
Ditto/pFedMe personal models, transport error-feedback accumulators — is
a single float32 ``(m, dim_aligned)`` *slab* per entry, laid out by a
static :class:`repro.core.flat.LayoutTable` built once from ``params0``
at strategy construction. Pytree structure reappears ONLY at ``apply_fn``
boundaries: ``layout.unravel`` before local SGD / evaluation,
``layout.ravel`` on the way back. Because a bare matrix is a single-leaf
pytree, every tree-generic helper here (:class:`StateOps`, the mesh
row-sharding, :func:`fedavg_masked_mix`, the sentinel scatters) operates
on slabs unchanged — and always hits the fused single-leaf
``masked_mix_scatter`` / HBM gather-mix-scatter kernel path, multi-leaf
model or not. The ``dim_aligned - dim`` tail columns are zero by
construction (``LayoutTable.ravel`` zero-fills them); all mixes are
column-independent, so the tail never contaminates values, norms or the
streaming Δ/σ² statistics.

Donation rules: jax actually honors ``donate_argnums`` on CPU and TPU —
after a masked round the *input* state buffers are dead. Donated per
body: the ``params`` slab always; the ``ef`` transport accumulator,
``refresh`` buffers and the async ``abuf`` whenever the owning knob is
on (they are rewritten every cohort round). ``W`` and ``collab`` are
never donated. The simulation loop always rebinds the state, and its
warm-up call runs on a copy; any direct caller that wants to keep the
pre-round state alive must copy it first (see
tests/test_masked_cohort.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import aggregation, similarity
from repro.core.pytree import (  # noqa: F401  (re-export)
    gather_rows, scatter_rows, stacked_ravel, stacked_unravel,
    tree_count_params,
)
from repro.federated import async_buffer
from repro.federated import mesh as mesh_lib
from repro.federated import participation
from repro.federated import spans
from repro.federated import topology as topology_lib
from repro.federated import transport as transport_lib
from repro.kernels import ops


def broadcast_params(params0, m):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (m,) + x.shape) + 0.0, params0
    )


def group_mixing_matrix(assignment, n):
    """Row-stochastic W implementing per-group FedAvg (CFL/Oracle).

    W[i, j] = n_j · 1[a_i == a_j] / Σ_{a_k == a_i} n_k.
    """
    same = (assignment[:, None] == assignment[None, :]).astype(jnp.float32)
    w = same * n.astype(jnp.float32)[None, :]
    return w / jnp.sum(w, axis=1, keepdims=True)


def group_average(stacked, assignment, n, *, impl=None):
    w = group_mixing_matrix(assignment, n)
    return aggregation.user_centric(stacked, w, impl=impl)


# ------------------------------------------------------------ W refresh hook

def w_refresh_hook(refresh_cfg):
    """Build the in-jit streaming W-refresh step for W-owning strategies.

    Returns ``None`` when the knob is off, else a traceable
    ``hook(pre_flat, post_flat, refresh, idx, mask, n) -> (refresh', W')``
    where ``pre_flat``/``post_flat`` are the (c, d) raveled cohort params
    before/after local SGD (the upload the round already has — refreshing
    W adds NO uplink bytes). The hook introduces no new shapes, so one
    compiled round per policy still holds (recompile-guard tested in
    tests/test_w_refresh.py).
    """
    if refresh_cfg is None:
        return None

    def hook(pre_flat, post_flat, refresh, idx, mask, n):
        with spans.scope(spans.W_REFRESH):
            obs = similarity.grad_proxy(pre_flat, post_flat)
            return similarity.streaming_refresh(refresh, obs, idx, mask, n,
                                                cfg=refresh_cfg)

    return hook


def staleness_metrics(refresh):
    """Round metrics for the refresh buffers: the per-client staleness
    counters plus their max/mean (device scalars, like ``streams`` — no
    host sync in-round)."""
    stale = refresh["staleness"]
    return {"staleness": stale, "staleness_max": jnp.max(stale),
            "staleness_mean": jnp.mean(stale.astype(jnp.float32))}


def refresh_skip_round(state):
    """``Strategy.skip_round`` hook for W-refresh strategies.

    A round nobody attends still ages every client's Δ/σ² statistics:
    the per-client staleness counters advance exactly as
    :func:`repro.core.aggregation.staleness_update` would with an
    all-masked cohort (bump everyone, reset nobody). Without this, an
    all-offline round between two refresh rounds under-reported
    staleness by one — the simulation loop used to skip strategy state
    entirely.
    """
    refresh = state["refresh"]
    return dict(state,
                refresh=dict(refresh, staleness=refresh["staleness"] + 1))


# ----------------------------------------------------------- state layout

class StateOps:
    """Layout-aware primitives over the (m, ·) stacked server state.

    One object per strategy, built from the ``FedConfig`` knobs
    (``StateOps(cfg.mesh, cfg.shard_state)``) and closed over by the
    jitted round bodies, so every gather/scatter/mix against the stacked
    state goes through ONE dispatch point:

      * replicated (``shard_state=False``, the default): every method is
        exactly the pre-existing helper (``gather_rows``,
        ``aggregation.mix_scatter``, :func:`fedavg_masked_mix`, ...) —
        bit-exact with the unsharded engine, mesh or not.
      * row-sharded (``shard_state=True``): the state's leading axis
        lives partitioned across the ``clients`` mesh (see the
        row-sharded section of :mod:`repro.federated.mesh`); gathers
        assemble the cohort with a (c, d) psum, scatters/mixes rewrite
        only the owner shard's block, and the buffered-async flush's
        tiled all-gather of its (B, d) buffer is the only model-sized
        collective. Requires a mesh and ``m % num_shards == 0``.

    Cohort-shaped intermediates (the (c, ·) gathered trees, (c, c) mix
    rows, per-slot arrays) are always replicated — only (m, ·) /(B, ·)
    stacked state changes layout.
    """

    def __init__(self, mesh=None, shard_state: bool = False):
        mesh = mesh_lib.resolve(mesh)
        if shard_state and mesh is None:
            raise ValueError(
                "FedConfig.shard_state requires a mesh (FedConfig.mesh): "
                "row-sharding partitions the state across the clients "
                "mesh's devices")
        self.mesh = mesh
        self.sharded = bool(shard_state)

    # ---- cohort row movement

    def gather(self, tree, safe):
        """Cohort gather ``tree[safe]`` (``safe`` pre-clamped).

        Replicated single-leaf states route through the HBM-resident
        row gather (:func:`aggregation.cohort_gather` —
        bit-identical to ``gather_rows``, O(c·d) traffic at any m)."""
        if self.sharded:
            return mesh_lib.shard_gather_rows(tree, safe, self.mesh)
        return aggregation.cohort_gather(tree, safe)

    def scatter(self, tree, idx, updates):
        """Sentinel-drop cohort scatter (pads never write)."""
        if self.sharded:
            return mesh_lib.shard_scatter_rows(tree, idx, updates,
                                               self.mesh)
        return scatter_rows(tree, idx, updates)

    # ---- fused PS mixes

    def _replicated(self, fn):
        # replicated layout on a mesh: the PS kernels run per device
        # inside a shard_map (Mosaic kernels cannot be auto-partitioned)
        return fn if self.mesh is None else mesh_lib.replicated(fn,
                                                                self.mesh)

    def mix_scatter(self, full, cohort_updated, rows, idx, mask, *,
                    impl=None):
        """:func:`repro.core.aggregation.mix_scatter` in either layout."""
        return self.mix_scatter_flat(full, stacked_ravel(cohort_updated),
                                     rows, idx, mask, impl=impl)

    def mix_scatter_flat(self, full, flat_c, rows, idx, mask, *,
                         impl=None, flat_sharded=False):
        """:func:`repro.core.aggregation.mix_scatter_flat` in either
        layout. Sharded: the (c, c) × (c, d) mix is computed redundantly
        per device (c ≪ m — cheaper than a collective) and each device's
        fused kernel rewrites only the rows its block owns (localized
        indices; non-owned slots become per-block sentinels).
        ``flat_sharded=True`` marks ``flat_c`` as itself row-sharded
        (the async buffer's pending ``upd`` rows): it is all-gathered
        INSIDE the same shard_map — the flush's one model-sized
        collective."""
        if not self.sharded:
            return self._replicated(functools.partial(
                aggregation.mix_scatter_flat, impl=impl))(full, flat_c,
                                                          rows, idx, mask)
        update = mesh_lib.shard_block_update(
            lambda block, loc, lm, fc, w: aggregation.mix_scatter_flat(
                block, fc, w, loc, lm, impl=impl),
            self.mesh, gather_args=1 if flat_sharded else 0)
        return update(full, idx, mask, flat_c, rows)

    def fedavg_mix(self, params, updated, idx, mask, n, *, impl=None):
        """:func:`fedavg_masked_mix` in either layout (the (1, c) mix is
        replicated; sharded states broadcast it block-wise)."""
        if not self.sharded:
            return self._replicated(functools.partial(
                fedavg_masked_mix, impl=impl))(params, updated, idx, mask,
                                               n)
        safe = aggregation.safe_gather_index(idx, n.shape[0])
        w = aggregation.masked_fedavg_weights(jnp.take(n, safe), mask)
        mixed = aggregation.user_centric(updated, w, impl=impl)
        return mesh_lib.shard_broadcast_rows(params, mixed,
                                             jnp.any(mask), self.mesh)

    def constrain(self, tree):
        """Pin a traced (m, ·) output to the state's layout (no-op when
        replicated) — used where plain jnp ops produce the new state."""
        if self.sharded:
            return mesh_lib.constrain_rows(tree, self.mesh)
        return tree

    # ---- host-side commits (outside jit)

    def commit_state(self, state, shard_keys=("params",)):
        """Commit a strategy-state dict to its steady-state shardings.

        ``shard_keys`` names the row-sharded (m, ·) entries; everything
        else (W, cohort bookkeeping, refresh buffers, host leaves) is
        replicate-committed. No-op without a mesh, copy-free once
        committed — the dispatcher calls this every round to keep the
        one-compilation guarantee (see :func:`mesh.commit_replicated`).
        """
        if self.mesh is None:
            return state
        if not self.sharded:
            return mesh_lib.commit_replicated(state, self.mesh)
        out = dict(state)
        for k, v in state.items():
            if k in shard_keys:
                out[k] = mesh_lib.commit_rows(v, self.mesh)
            elif k == "abuf" and v is not None:
                out[k] = self.commit_buffer(v)
            else:
                out[k] = mesh_lib.commit_replicated(v, self.mesh)
        return out

    # ---- buffered-async buffer layout

    @property
    def buffer_shards(self) -> int:
        """Shard count the async buffer's B axis must divide by."""
        return mesh_lib.num_shards(self.mesh) if self.sharded else 1

    def buffer_scatter(self):
        """Deposit hook for :func:`repro.federated.async_buffer.deposit`:
        routes each upload row to its owner shard of the row-sharded
        (B, d) ``upd`` array. None (plain ``.at[].set``) when replicated.
        """
        if not self.sharded:
            return None
        mesh = self.mesh
        return lambda upd, dest, rows: mesh_lib.shard_scatter_rows(
            upd, dest, rows, mesh)

    def buffer_gather(self, upd):
        """Replicate the pending-upload rows for a flush — the ONLY
        model-sized collective of the sharded async engine."""
        if self.sharded:
            return mesh_lib.all_gather_rows(upd, self.mesh)
        return upd

    def commit_buffer(self, buf):
        """Commit an async buffer: ``upd`` row-sharded, metadata
        (idx/ver/count/version/last_sync) replicated."""
        if self.mesh is None:
            return buf
        if not self.sharded:
            return mesh_lib.commit_replicated(buf, self.mesh)
        meta = {k: v for k, v in buf.items() if k != "upd"}
        out = mesh_lib.commit_replicated(meta, self.mesh)
        out["upd"] = mesh_lib.commit_rows(buf["upd"], self.mesh)
        return out


# ------------------------------------------------------------------ engine

def cohort_round(dense_fn, masked_fn, *, masked_jit=None, mesh=None,
                 async_fn=None, async_cfg=None, sops=None,
                 shard_keys=("params",), upload_stage=None,
                 transport=None, topology=None):
    """Build ``round(state, data, key, cohort=None)`` from the two paths.

    Args:
      dense_fn: ``(state, data, key) -> (state, metrics)`` — the legacy
        full-participation path (must stay bit-exact with the pre-cohort
        engine).
      masked_fn: ``(state, data, key, idx, mask) -> (state, metrics)`` —
        the fixed-shape padded-cohort path; ``idx``/``mask`` are the
        device-side (c,) slot arrays.
      masked_jit: optional handle on the underlying jitted masked body,
        attached to the returned function as ``round.masked_jit`` so
        tests can assert the one-compilation guarantee via
        ``_cache_size()``.
      mesh: optional client-axis mesh knob (``FedConfig.mesh``; see
        :mod:`repro.federated.mesh`). Every cohort is padded to a slot
        count divisible by the shard count before dispatch, so the
        shard_mapped local-SGD stage inside ``masked_fn`` always sees an
        evenly partitionable slot axis; the extra sentinel slots are
        bit-invisible and the padded count is the same every round, so
        the one-compilation guarantee holds under a fixed mesh.
      async_fn: the strategy's buffered-async cohort body (same
        signature as ``masked_fn``), used in place of it when
        ``async_cfg`` is set — the one dispatch point is how ALL
        strategies share the ``FedConfig.async_buffer`` knob.
      async_cfg: the ``FedConfig.async_buffer`` value. Setting it
        without an ``async_fn`` raises ``NotImplementedError`` here, at
        construction time: the strategy's PS step has no buffered form
        (SCAFFOLD controls, Ditto/pFedMe personal models, FedFomo
        client-side mixing, ucfl_parallel's m× streams).
      sops: the strategy's :class:`StateOps` (built from
        ``FedConfig.mesh`` / ``FedConfig.shard_state``). When it is
        row-sharded, the state is committed per ``shard_keys`` (the
        names of the (m, ·) stacked entries) and ``cohort=None`` raises
        — the dense path trains every client and broadcasts the whole
        state, which is exactly the O(m·d) traffic shard_state removes.
      upload_stage: the strategy's fault/robust upload rewrite
        (:func:`repro.federated.faults.upload_stage`), passed here ONLY
        so the dispatcher can reject ``cohort=None``: faults and robust
        rules are masked-slot transforms with no dense counterpart, so
        the dense path raises at call time (the masked bodies already
        closed over the stage themselves).
      transport: the ``FedConfig.transport`` value, passed here ONLY so
        the dispatcher can reject ``cohort=None`` — quantization rewrites
        the masked upload stage, and the dense path has no upload.
      topology: the ``FedConfig.topology`` value, passed here ONLY so
        the dispatcher can reject ``cohort=None`` — the two-tier engine
        partitions the COHORT's upload slots over edges, and the dense
        path has no per-edge upload stage (the masked bodies already
        closed over the tiered mix themselves).

    The returned ``round`` accepts ``cohort=None`` (dense), a
    :class:`~repro.federated.participation.Cohort`, or a plain index
    array (normalized to an unpadded all-real cohort).
    """
    if async_cfg is not None and async_fn is None:
        raise NotImplementedError(
            "FedConfig.async_buffer is set but this strategy has no "
            "buffered-async aggregation rule (supported: ucfl "
            "full/clustered and the FedAvg family — strategies whose PS "
            "step is the masked row aggregation)")
    use_async = async_cfg is not None
    mesh = mesh_lib.resolve(mesh)
    sharded = sops is not None and sops.sharded

    def round(state, data, key, cohort=None):
        if sops is not None and sops.mesh is not None:
            # commit the state so round 1 already enters with the
            # steady-state input shardings (replicated, or row-sharded
            # per shard_keys) — otherwise jit would compile a second,
            # post-warm-up entry when round 2 first sees a committed
            # state. No-op after the first round.
            state = sops.commit_state(state, shard_keys)
        elif mesh is not None:
            state = mesh_lib.commit_replicated(state, mesh)
        cohort = participation.as_cohort(cohort, data.num_clients)
        if cohort is None:
            if sharded:
                raise ValueError(
                    "FedConfig.shard_state requires cohort rounds: "
                    "cohort=None is the dense full-participation path, "
                    "whose broadcast is the O(m·d) traffic row-sharding "
                    "removes — pass a participation config (or drop "
                    "shard_state)")
            if use_async:
                raise ValueError(
                    "the buffered-async engine processes arrival cohorts; "
                    "cohort=None is the bulk-synchronous dense path — pass "
                    "a participation config (or drop FedConfig.async_buffer)")
            if upload_stage is not None:
                raise ValueError(
                    "FedConfig.faults/robust require cohort rounds: the "
                    "injection and robust rewrites are fixed-shape masked "
                    "slot transforms with no dense counterpart — pass a "
                    "participation config (or drop faults/robust)")
            if transport is not None:
                raise ValueError(
                    "FedConfig.transport requires cohort rounds: "
                    "quantization compresses the masked upload stage, and "
                    "the dense full-participation path has no upload — "
                    "pass a participation config (or drop transport)")
            if topology is not None:
                raise ValueError(
                    "FedConfig.topology requires cohort rounds: the "
                    "two-tier engine partitions the cohort's upload slots "
                    "over edge aggregators, and the dense "
                    "full-participation path has no per-edge upload stage "
                    "— pass a participation config (or drop topology)")
            state, metrics = dense_fn(state, data, key)
            size = data.num_clients
        else:
            if mesh is not None:
                cohort = mesh_lib.pad_cohort(cohort, mesh, data.num_clients)
            # idx/mask stay host numpy here (jit converts at dispatch), so
            # wrappers can derive host-side metrics without a device sync
            fn = async_fn if use_async else masked_fn
            state, metrics = fn(state, data, key, cohort.indices,
                                cohort.mask)
            size = len(cohort)
        return state, {**metrics, "cohort_size": size}

    round.masked_jit = masked_jit
    return round


def cohort_keys(key, m, safe_idx):
    """Client-indexed per-slot PRNG keys for the masked cohort round.

    Splits the round key by the STATIC client count m and gathers the
    rows at the cohort's (clamped) indices, so a slot's key depends only
    on its client id — not on the slot count or cohort composition. This
    makes padded cohorts reproduce unpadded ones bit-for-bit, and a full
    cohort reproduce the dense path's ``split(key, m)`` exactly.
    """
    return jnp.take(jax.random.split(key, m), safe_idx, axis=0)


def make_masked_round(train, mix, *, donate=True, sops=None,
                      upload_stage=None, layout=None, transport=None,
                      schema=None):
    """Jit the standard masked round body with a donated params buffer.

    With ``layout`` (a :class:`repro.core.flat.LayoutTable` — the slab
    engine, used by every strategy):

    train(pc_tree, xc, yc, keys, *args) -> cohort-stacked updated tree
      (the body unravels the gathered (c, d_al) slab rows for it and
      ravels its result back — the ONLY tree boundary in the round)
    mix(params_slab, post_flat, idx, mask, *args) -> new (m, d_al) slab

    Without ``layout`` the legacy tree contract holds (``mix`` receives
    the cohort-stacked updated TREE) — kept for direct callers/tests.

    ``*args`` is an arbitrary tuple of device arrays (W, labels, n, ...)
    threaded to both closures. ``donate=True`` passes
    ``donate_argnums=(0,)`` so the stacked state is consumed in place.

    ``transport`` (``FedConfig.transport``; requires ``layout``) inserts
    the quantize→dequantize delta stage with error feedback between
    local SGD and the upload stage: the returned body then takes AND
    returns the (m, W_ul) ``ef`` accumulator slab as its second donated
    argument — ``body(params, ef, idx, mask, x, y, key, *args) ->
    (mix(...), ef')``. ``schema`` (the strategy's
    :class:`~repro.federated.transport.WireSchema`) keys the stage: the
    per-stream :func:`~repro.federated.transport.make_wire_stage` over
    the schema's concatenated uplink slab (W_ul = its aligned width;
    a single-delta schema is bit-identical to the legacy single-slab
    stage, which ``schema=None`` keeps for direct callers/tests).
    ``transport=None`` keeps the stage (and the extra argument) out of
    the trace entirely — bit-exact with the transport-free engine.
    Downlink compression is a MIX concern (the served payload is mix
    output): see :func:`fedavg_mix_closure` for the broadcast family's
    compressed-downlink mix; ``mix`` results are opaque to this body, so
    a downlink-compressing mix simply returns ``(new_state, ef_dl')``
    with the server-side EF threaded through ``*args``.

    ``upload_stage`` (:func:`repro.federated.faults.upload_stage`) is the
    fault-injection / finite-guard / robust rewrite applied between
    local SGD (and the transport stage — faults corrupt what the wire
    carried) and ``mix``: it sees the (c, d) pre/post upload slab plus
    the slot arrays and hands ``mix`` the rewritten upload and
    ``idx``/``mask`` (demoted slots carry the sentinel, so the fused
    scatter drops them). ``None`` (the default) keeps the exact
    pre-existing trace — bit-exact with the stage-free engine.

    Sharding: when the strategy's ``local`` was built with a mesh
    (``FedConfig.mesh``), ``train`` runs under shard_map with the cohort
    slots partitioned across devices and its per-slot results
    all-gathered (see :func:`repro.federated.client.client_vmap`), so
    ``mix`` — the tiny (c, c) rules and the fused scatter over the
    (m, d) state — always operates on replicated cohort arrays. The
    state itself is replicated unless ``sops`` is row-sharded
    (``FedConfig.shard_state``), in which case the round-start gather
    routes through the owner shards (``mix`` closures must use the same
    ``sops`` for their scatters; the ``ef`` slab rides the same layout).
    The dispatcher pads slot counts to a shard multiple
    (:func:`cohort_round`'s ``mesh`` arg).
    """
    gather = sops.gather if sops is not None else (
        lambda tree, safe: gather_rows(tree, safe))
    scatter = sops.scatter if sops is not None else scatter_rows
    if schema is not None:
        tstage = transport_lib.make_wire_stage(schema, transport, "uplink")
    else:
        tstage = transport_lib.make_stage(transport)
    if tstage is not None and layout is None:
        raise ValueError("transport requires the slab layout table")

    def core(params, ef, idx, mask, x, y, key, *args):
        with spans.scope(spans.GATHER):
            safe = aggregation.safe_gather_index(idx, x.shape[0])
            keys = cohort_keys(key, x.shape[0], safe)
            pc = gather(params, safe)
            xc, yc = x[safe], y[safe]
            tree = layout.unravel(pc) if layout is not None else pc
        if layout is not None:
            updated = train(tree, xc, yc, keys, *args)
            post = layout.ravel(updated)
            if tstage is not None:
                # the EF rows ride the cohort: gathered at the clamped
                # indices, scattered back at the ORIGINAL slots (clients
                # keep their residual even if a later stage demotes
                # their upload — the loss happened on the wire)
                post, efc = tstage(pc, post, gather(ef, safe))
                ef = scatter(ef, idx, efc)
            if upload_stage is not None:
                post, idx, mask = upload_stage(pc, post, idx, mask, key,
                                               x.shape[0])
            with spans.scope(spans.MIX):
                return mix(params, post, idx, mask, *args), ef
        updated = train(tree, xc, yc, keys, *args)
        if upload_stage is not None:
            flat, idx, mask = upload_stage(
                stacked_ravel(pc), stacked_ravel(updated), idx, mask,
                key, x.shape[0])
            updated = stacked_unravel(updated, flat)
        with spans.scope(spans.MIX):
            return mix(params, updated, idx, mask, *args), ef

    if tstage is None:
        def body(params, idx, mask, x, y, key, *args):
            out, _ = core(params, None, idx, mask, x, y, key, *args)
            return out

        return jax.jit(body, donate_argnums=(0,) if donate else ())

    def body_t(params, ef, idx, mask, x, y, key, *args):
        return core(params, ef, idx, mask, x, y, key, *args)

    return jax.jit(body_t, donate_argnums=(0, 1) if donate else ())


def fedavg_masked_mix(params, updated, idx, mask, n, *, impl=None):
    """Masked Eq. 1: n-weighted cohort mean, broadcast to every row of
    ``params``.

    ``n`` must be the full (m,) dataset sizes — the sentinel pad indices
    are clamped against it, NOT against ``params`` (pFedMe passes the
    cohort-stacked local copies as ``params`` to get a cohort-shaped
    broadcast).
    """
    rows = jax.tree.leaves(params)[0].shape[0]
    safe = aggregation.safe_gather_index(idx, n.shape[0])
    w = aggregation.masked_fedavg_weights(jnp.take(n, safe), mask)
    mixed = aggregation.user_centric(updated, w, impl=impl)
    # an all-masked cohort has zero weight mass: keep the previous model
    # instead of broadcasting the degenerate zero mix (the engine skips
    # such rounds, but direct callers get safe semantics too)
    alive = jnp.any(mask)
    return jax.tree.map(
        lambda x, p: jnp.where(alive,
                               jnp.broadcast_to(x, (rows,) + x.shape[1:]), p),
        mixed, params)


def tiered_fedavg_weights(edge_arr, num_edges, slots, idx, mask, n):
    """Two-tier FedAvg weights over a padded cohort.

    Tier 1 applies the existing masked rule PER EDGE: the cohort's slot
    arrays are partitioned into fixed-shape ``(E, s)`` per-edge cohorts
    (:func:`repro.federated.topology.edge_partition`) and
    ``masked_fedavg_weights`` vmaps over them — each edge normalizes its
    own members' ``n`` mass, an empty edge gets all-zero weights. Tier 2
    is the same rule over the per-edge masses. Returns

      wpe (E, c) — tier-1 weights mapped back to cohort columns, so
                   ``wpe @ upload_slab`` is the (E, d) edge-aggregate
                   slab that crosses the edge↔PS backhaul;
      w2  (E,)   — tier-2 inter-edge weights (mass-proportional).

    Because ``w2[e]·wpe[e, j] = n_j / Σn`` wherever edge e has mass, the
    composition reproduces the flat n-weighted mean EXACTLY up to float
    association — matched accuracy is by construction, the PS-side
    saving is that only E aggregates transit the backhaul.
    """
    c = idx.shape[0]
    eidx, emask, eslot = topology_lib.edge_partition(
        edge_arr, num_edges, slots, idx, mask)
    esafe = aggregation.safe_gather_index(eidx, n.shape[0])
    ne = (jnp.take(n, esafe) * emask).astype(jnp.float32)  # (E, s)
    w1 = jax.vmap(aggregation.masked_fedavg_weights)(ne, emask)[:, 0, :]
    wpe = (jnp.zeros((num_edges, c), jnp.float32)
           .at[jnp.arange(num_edges)[:, None], eslot]
           .set(w1 * emask, mode="drop"))
    mass = jnp.sum(ne, axis=1)  # (E,)
    w2 = aggregation.masked_fedavg_weights(mass, mass > 0)[0]
    return wpe, w2


def fedavg_mix_closure(*, sops=None, impl=None, dstage=None, topology=None):
    """Build the FedAvg-family mix (masked Eq. 1, broadcast back).

    ``dstage=None`` returns the plain broadcast mix
    (:func:`fedavg_masked_mix` / ``sops.fedavg_mix``) — the exact
    pre-schema trace. With ``dstage`` (the schema's downlink
    :func:`~repro.federated.transport.make_wire_stage`) the served
    global is delta-coded against the receivers' shared reference — the
    OLD global, row 0 of the broadcast-uniform stacked state — with the
    server-side (1, W_dl) EF accumulator threaded as a trailing mix arg:
    ``mix(params, updated, idx, mask, n, ef_dl) -> (new, ef_dl')``. An
    all-masked cohort keeps params AND ef_dl unchanged (no wire
    activity — skip-round semantics, like the plain mix).

    ``topology`` (a :class:`repro.federated.topology.Topology`) swaps
    the single global mean for the two-tier factorization
    (:func:`tiered_fedavg_weights`): tier-1 edge aggregates, tier-2
    mass-weighted combine, then the identical broadcast/EF tail — so
    the tiered mix composes with the compressed downlink unchanged.
    ``None`` keeps the flat mix bit-exact.
    """
    if topology is not None:
        return _tiered_fedavg_mix_closure(topology, sops=sops,
                                          dstage=dstage)
    if dstage is None:
        if sops is None:
            return functools.partial(fedavg_masked_mix, impl=impl)

        def plain_mix(params, updated, idx, mask, n):
            return sops.fedavg_mix(params, updated, idx, mask, n,
                                   impl=impl)

        return plain_mix

    gather = sops.gather if sops is not None else (
        lambda tree, safe: gather_rows(tree, safe))

    def mix(params, updated, idx, mask, n, ef_dl):
        rows = jax.tree.leaves(params)[0].shape[0]
        safe = aggregation.safe_gather_index(idx, n.shape[0])
        w = aggregation.masked_fedavg_weights(jnp.take(n, safe), mask)
        mixed = aggregation.user_centric(updated, w, impl=impl)  # (1, W)
        ref = gather(params, jnp.zeros((1,), jnp.int32))
        served, new_ef = dstage(ref, mixed, ef_dl)
        alive = jnp.any(mask)
        ef_dl = jnp.where(alive, new_ef, ef_dl)
        if sops is not None and sops.sharded:
            new = mesh_lib.shard_broadcast_rows(params, served, alive,
                                                sops.mesh)
        else:
            new = jnp.where(
                alive,
                jnp.broadcast_to(served, (rows,) + served.shape[1:]),
                params)
        return new, ef_dl

    return mix


def _tiered_fedavg_mix_closure(topology, *, sops=None, dstage=None):
    """The two-tier FedAvg mix (see :func:`fedavg_mix_closure`).

    Tier-1 edge aggregates materialize as one ``(E, d)`` matmul over the
    upload slab, tier-2 as a length-E weighted sum — both inside the
    same jitted round body, so the tiered path keeps the one-compilation
    guarantee and O(c·d + E·d) cost. Construction-time guards upstream
    ensure ``sops`` is never row-sharded here.
    """
    edge_arr = topology.edge_array()
    num_edges = topology.num_edges

    def tiered_global(updated, idx, mask, n):
        c = idx.shape[0]
        slots = topology.slots_per_edge(c)
        wpe, w2 = tiered_fedavg_weights(edge_arr, num_edges, slots,
                                        idx, mask, n)

        def leaf(u):
            agg = wpe @ u.reshape(c, -1)  # (E, d) edge-aggregate slab
            return (w2 @ agg).reshape((1,) + u.shape[1:])

        return jax.tree.map(leaf, updated)

    def broadcast(params, mixed, mask):
        rows = jax.tree.leaves(params)[0].shape[0]
        alive = jnp.any(mask)
        return jax.tree.map(
            lambda x, p: jnp.where(
                alive, jnp.broadcast_to(x, (rows,) + x.shape[1:]), p),
            mixed, params)

    if dstage is None:
        def tmix(params, updated, idx, mask, n):
            return broadcast(params, tiered_global(updated, idx, mask, n),
                             mask)

        return tmix

    gather = sops.gather if sops is not None else (
        lambda tree, safe: gather_rows(tree, safe))

    def tmix_dl(params, updated, idx, mask, n, ef_dl):
        mixed = tiered_global(updated, idx, mask, n)  # (1, W)
        ref = gather(params, jnp.zeros((1,), jnp.int32))
        served, new_ef = dstage(ref, mixed, ef_dl)
        alive = jnp.any(mask)
        ef_dl = jnp.where(alive, new_ef, ef_dl)
        return broadcast(params, served, mask), ef_dl

    return tmix_dl


def make_fedavg_masked_round(local, *, train=None, impl=None, donate=True,
                             sops=None, upload_stage=None, layout=None,
                             transport=None, schema=None, topology=None):
    """The FedAvg-family masked round (FedAvg/FedProx reuse it).

    ``fedavg_masked_mix`` is tree-generic, so the same mix serves the
    legacy tree contract and the slab engine (where ``updated`` is the
    (c, d_al) upload matrix) unchanged. ``train`` overrides the default
    plain-local-SGD train closure (FedProx passes its proximal-centered
    one); it must accept ``(pc, xc, yc, keys, n, *extra)`` — the extra
    args carry the downlink EF when the schema compresses the broadcast.
    ``topology`` routes the mix through the two-tier engine (see
    :func:`fedavg_mix_closure`); ``None`` keeps the flat mix bit-exact.
    """

    if train is None:
        def train(pc, xc, yc, keys, n, *_):
            updated, _ = local(pc, xc, yc, None, keys=keys)
            return updated

    dstage = (transport_lib.make_wire_stage(schema, transport, "downlink")
              if schema is not None else None)
    mix = fedavg_mix_closure(sops=sops, impl=impl, dstage=dstage,
                             topology=topology)
    return make_masked_round(train, mix, donate=donate, sops=sops,
                             upload_stage=upload_stage, layout=layout,
                             transport=transport, schema=schema)


# ------------------------------------------------------- buffered-async path

def state_async_buffer(state, acfg, m, slots, dim, sops=None, schema=None):
    """Fetch — or lazily create — the strategy state's upload buffer.

    The buffer's slot count depends on the participation policy's cohort
    slot count, which the strategy cannot know at ``init`` time, so the
    first cohort round creates it here (host-side, outside jit; the
    shapes are the same every round, so the one-compilation guarantee is
    unaffected — a warm-up that discards its state merely re-creates the
    same-shaped zeros on round 1).

    ``sops`` commits the fresh buffer to its steady-state layout —
    replicated over the mesh, or (``shard_state``) ``upd`` row-sharded
    with B padded to a shard multiple — exactly like the dispatcher
    commits the rest of the state: a buffer born uncommitted on round 1
    would re-enter round 2 with the round's output sharding and trigger
    a second compile.
    """
    buf = state.get("abuf")
    if buf is None:
        shards = sops.buffer_shards if sops is not None else 1
        buf = async_buffer.init_buffer(acfg, m, slots, dim, shards=shards,
                                       schema=schema)
        if sops is not None:
            buf = sops.commit_buffer(buf)
    return buf


def make_fedavg_async_round(train, acfg, *, impl=None, sops=None,
                            upload_stage=None, layout=None,
                            transport=None, schema=None):
    """The FedAvg-family buffered-async round (FedAvg/FedProx reuse it).

    FedBuff's server rule in delta form: the buffer holds the cohort's
    model DELTAS ``θ_upload − θ_base`` (each computed against the global
    model current at its upload round), and a flush adds the n-weighted
    mean delta to the current global — with an all-fresh buffer this
    reproduces the barrier :func:`fedavg_masked_mix` exactly
    (θ + Σ w̃(u − θ) = Σ w̃ u). Mixing raw stale MODELS instead would
    drag the global back toward old versions, which is why the delta
    form is load-bearing here.

    Honest staleness note: under the flush-the-whole-buffer rule the
    FedAvg-family τ is STRUCTURALLY ZERO — the server version only moves
    at a flush, a flush clears every pending slot, and a client samples
    the current global at deposit, so no upload can ever outlive a
    version bump. The ``(1+τ)^{-α}`` machinery is kept in the shared
    body (a partial-flush rule would make it live, and the user-centric
    rules — whose base is the client's own last-rewritten row — exercise
    it for real), but for this family the discount never engages and
    ``tau_max``/``tau_mean`` report 0.

    ``train(pc, xc, yc, keys, n) -> updated`` as in
    :func:`make_fedavg_masked_round` (``layout`` unravels/ravels around
    it on the slab engine). Returns a jitted
    ``body(params, abuf, idx, mask, x, y, key, n) ->
    (params', abuf', metrics)`` with ``params`` AND the buffer donated —
    or, with ``transport`` on, ``body(params, ef, abuf, ...) ->
    (params', ef', abuf', metrics)`` with all three donated: the delta
    is quantized (error-feedback carried in ``ef``) BEFORE it is
    deposited, so the pending buffer holds exactly what the wire
    carried. ``sops`` picks the state/buffer layout (row-sharded
    deposits route each upload to its owner shard; the flush all-gathers
    the (B, d) rows — the engine's only model-sized collective).
    """
    flush_k = int(acfg.flush_k)
    gather = sops.gather if sops is not None else (
        lambda tree, safe: gather_rows(tree, safe))
    scatter = sops.buffer_scatter() if sops is not None else None
    efscatter = sops.scatter if sops is not None else scatter_rows
    if schema is not None:
        tstage = transport_lib.make_wire_stage(schema, transport, "uplink")
    else:
        tstage = transport_lib.make_stage(transport)
    if tstage is not None and layout is None:
        raise ValueError("transport requires the slab layout table")

    def core(params, ef, abuf, idx, mask, x, y, key, n):
        m = x.shape[0]
        with spans.scope(spans.GATHER):
            safe = aggregation.safe_gather_index(idx, m)
            keys = cohort_keys(key, m, safe)
            pc = gather(params, safe)
            xc, yc = x[safe], y[safe]
            tree = layout.unravel(pc) if layout is not None else pc
        updated = train(tree, xc, yc, keys, n)
        if layout is not None:
            pre_flat, post_flat = pc, layout.ravel(updated)
        else:
            pre_flat = stacked_ravel(pc)
            post_flat = stacked_ravel(updated)
        if tstage is not None:
            post_flat, efc = tstage(pre_flat, post_flat, gather(ef, safe))
            ef = efscatter(ef, idx, efc)
        if upload_stage is not None:
            # faults/guard/robust rewrite the upload BEFORE it is
            # deposited: demoted slots carry the sentinel, so their junk
            # delta rows never enter the pending buffer
            post_flat, idx, mask = upload_stage(pre_flat, post_flat, idx,
                                                mask, key, m)
        with spans.scope(spans.MIX):
            delta = post_flat - pre_flat
            # FedAvg clients download the CURRENT global when sampled, so the
            # upload's base version is the version at deposit time
            base_ver = jnp.broadcast_to(abuf["version"], idx.shape)
            abuf = async_buffer.deposit(abuf, delta, idx, mask, base_ver, m,
                                        scatter=scatter)
            flush = abuf["count"] >= flush_k
            weights = async_buffer.staleness_weights(abuf, m, acfg.alpha)
            tau = async_buffer.staleness(abuf)
            applied = abuf["count"]
            bvalid = async_buffer.valid_mask(abuf, m)
            bsafe = aggregation.safe_gather_index(abuf["idx"], m)

            def do_flush(params, abuf):
                w = aggregation.masked_fedavg_weights(jnp.take(n, bsafe),
                                                      bvalid, weights)
                upd = (sops.buffer_gather(abuf["upd"]) if sops is not None
                       else abuf["upd"])
                # (1, d_al); stacked_unravel ignores the aligned-width tail
                step = ops.mix_aggregate(w, upd, impl=impl)
                new = jax.tree.map(jnp.add, params,
                                   stacked_unravel(params, step))
                return new, async_buffer.flush_reset(abuf, m)

            params, abuf = jax.lax.cond(flush, do_flush,
                                        lambda p, b: (p, b), params, abuf)
            if sops is not None:
                # the flush's broadcast add is plain jnp — pin the output to
                # the committed layout so round 2 doesn't recompile
                params = sops.constrain(params)
            metrics = async_buffer.flush_metrics(flush, applied, tau, weights,
                                                 abuf["count"])
            # one broadcast stream hits the downlink only when a flush ships
            # a new global
            metrics["streams"] = flush.astype(jnp.int32)
        return params, ef, abuf, metrics

    if tstage is None:
        def body(params, abuf, idx, mask, x, y, key, n):
            params, _, abuf, metrics = core(params, None, abuf, idx, mask,
                                            x, y, key, n)
            return params, abuf, metrics

        return jax.jit(body, donate_argnums=(0, 1))

    def body_t(params, ef, abuf, idx, mask, x, y, key, n):
        return core(params, ef, abuf, idx, mask, x, y, key, n)

    return jax.jit(body_t, donate_argnums=(0, 1, 2))


def fedavg_async_wrapper(train, params0, acfg, *, impl=None, sops=None,
                         upload_stage=None, layout=None, transport=None,
                         schema=None):
    """Build the FedAvg-family buffered-async cohort body + jit handle.

    Returns ``(amasked, jitted_body)`` for ``cohort_round(async_fn=...,
    masked_jit=...)``, or ``(None, None)`` when the knob is off.
    ``train`` as in :func:`make_fedavg_async_round`; the body manages the
    lazily-created buffer in ``state["abuf"]`` (and, with ``transport``
    on, the error-feedback slab in ``state["ef"]``), committed to the
    layout ``sops`` (the strategy's :class:`StateOps`) picks. ``schema``
    sizes the buffer rows at the uplink wire-slab width and keys the
    per-stream transport stage (the async downlink stays raw f32 — see
    the transport capability matrix).
    """
    if acfg is None:
        return None, None
    body = make_fedavg_async_round(train, acfg, impl=impl, sops=sops,
                                   upload_stage=upload_stage,
                                   layout=layout, transport=transport,
                                   schema=schema)
    dim = tree_count_params(params0)

    def amasked(state, data, key, idx, mask):
        abuf = state_async_buffer(state, acfg, data.num_clients,
                                  idx.shape[0], dim, sops, schema)
        if transport is None:
            new, abuf, metrics = body(state["params"], abuf, idx, mask,
                                      data.x, data.y, key, data.n)
            return dict(state, params=new, abuf=abuf), metrics
        new, ef, abuf, metrics = body(state["params"], state["ef"], abuf,
                                      idx, mask, data.x, data.y, key,
                                      data.n)
        return dict(state, params=new, ef=ef, abuf=abuf), metrics

    return amasked, body
