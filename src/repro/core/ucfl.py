"""The paper's proposed method: User-Centric Federated Learning.

Algorithm 1 end-to-end:
  1. special round — broadcast θ⁰; clients upload full gradients + σ_k²
     (Eq. 10) on a fixed minibatch partition of size ``var_batch_size``
     (a hyperparameter, §V-F);
  2. PS computes Δ and the mixing matrix W (Eq. 9);
  3. optionally K-means over rows of W to m_t centroid rules (§IV-B),
     picked by silhouette (Alg. 2) when ``num_streams="auto"``;
  4. every round: clients run ClientUpdate from their personalized model;
     PS applies the user-centric (or clustered) aggregation.

Cohort rounds use the fixed-shape masked engine (see
:mod:`repro.core.baselines.common`): the padded ``(indices, mask)`` slots
compile one round shape, the stacked-params buffer is donated, the PS mix
runs as one fused ``masked_mix_scatter`` kernel pass, and the downlink
stream count is computed on device from cluster-membership one-hots
precomputed at init (no per-round ``np.unique`` host sync).

State layout
------------
``init`` returns a dict of stacked device state plus host bookkeeping:

  * ``params`` — (m, ...) client-stacked personalized models;
  * ``W`` — the (m, m) mixing matrix (static without refresh, replaced
    every cohort round with refresh on);
  * ``labels`` / ``cluster_onehot`` / ``streams`` — clustered-variant
    bookkeeping (labels are fixed at init even under refresh: the
    downlink group structure stays static so one compiled round and a
    stable stream count survive — re-clustering is a host-side concern a
    caller can layer on top);
  * ``collab`` — the special round's raw statistics (kept for
    diagnostics/benchmarks; never donated);
  * ``refresh`` — only with ``FedConfig.w_refresh`` on: the streaming
    Δ/σ²/gradient-proxy/staleness buffers
    (:func:`repro.core.similarity.init_refresh_state`);
  * ``abuf`` — only with ``FedConfig.async_buffer`` on: the fixed-shape
    pending-upload buffer of the buffered-async server
    (:mod:`repro.federated.async_buffer`), created lazily on the first
    cohort round (its slot count is a participation-policy property).

Donation caveat: the jitted masked round donates BOTH the stacked
``params`` tree and (when present) the ``refresh`` or ``abuf`` buffers —
they are rewritten every cohort round. Callers that keep a pre-round state alive
must copy it (:func:`repro.federated.simulation.donation_safe_copy`
copies every ``jax.Array`` leaf, refresh buffers included); ``W`` and
``collab`` are not donated, so the init-time collaboration statistics
stay readable for the whole run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import aggregation, clustering, flat, similarity
from repro.core.baselines import common
from repro.core.pytree import gather_rows, stacked_ravel, tree_count_params
from repro.core.strategy import FedConfig, Strategy, register
from repro.data.loader import fixed_partition
from repro.federated import async_buffer
from repro.federated import client as fedclient
from repro.federated import faults as faults_lib
from repro.federated import mesh as mesh_lib
from repro.federated import spans
from repro.federated import topology as topology_lib
from repro.federated import transport as transport_lib
from repro.kernels import ops


# The most Eq. 10 minibatch samples the special round's gradient program
# takes at once when no chunk is set: the paper cell's whole batch, 20
# clients of 500 samples, which it runs unchunked.
INIT_CHUNK_SAMPLES = 20 * 500


def init_chunk(m, n, var_batch_size, chunk_size=None):
    """Clients per chunk of the special round's gradient program; m (or
    more) runs the whole client axis at once.

    An explicit ``chunk_size`` wins. Otherwise the chunk is the largest
    divisor of m whose clients hold at most ``INIT_CHUNK_SAMPLES`` samples
    of Eq. 10 minibatches (a client of n samples holds
    ``n // var_batch_size`` of them), so no chunk is padded; where that
    divisor holds under half of it (m prime, say), the most clients that
    fit, with a padded last chunk."""
    if chunk_size is not None:
        return chunk_size
    per_client = max(n // var_batch_size * var_batch_size, 1)
    most = min(max(INIT_CHUNK_SAMPLES // per_client, 1), m)
    best = max(c for c in range(1, most + 1) if m % c == 0)
    return best if 2 * best * per_client >= INIT_CHUNK_SAMPLES else most


@functools.partial(jax.jit, static_argnames=("apply_fn", "var_batch_size",
                                             "chunk_size", "mesh"))
def _special_round_uploads(params0, x, y, *, apply_fn, var_batch_size,
                           chunk_size, mesh):
    # every client's Eq. 10 upload, (m, d) full gradients and (m,) σ²
    grad_fn = jax.grad(fedclient.make_loss(apply_fn))

    def one_client(x, y):
        xb, yb = fixed_partition(x, y, var_batch_size)
        g = jax.vmap(grad_fn, in_axes=(None, 0, 0))(params0, xb, yb)
        gmat = stacked_ravel(g, lead=1)  # (K, d)
        full = jnp.mean(gmat, axis=0)
        return full, similarity.sigma_sq(gmat, full)

    with spans.scope(spans.INIT_GRADIENTS):
        return fedclient.client_vmap(one_client, chunk_size=chunk_size,
                                     mesh=mesh)(x, y)


@jax.jit
def _delta_and_weights(gram, sig, n):
    # Δ from the Gram matrix, then Eq. 9's W
    with spans.scope(spans.INIT_WEIGHTS):
        delta = ops.delta_from_gram(gram)
        return delta, similarity.mixing_weights(delta, sig,
                                                n.astype(jnp.float32))


def compute_collaboration(apply_fn, params0, data, *, var_batch_size=100,
                          impl=None, chunk_size=None, mesh=None):
    """Run the special pre-training round; returns the dict of §IV-A.

    Three compiled launches, each under its own host span and, on the
    device, a stage scope of the same name: ``fl.init.gradients`` the
    per-client gradient pass with σ² (one program, compiled once per
    ``apply_fn``, sizes and settings, so a later init on the same shapes
    compiles nothing), ``fl.init.gram`` the Gram program (``gram_pallas``
    on the chip, a program of its own) and ``fl.init.weights`` Δ and W
    from the Gram.

    The gradient pass goes over the client axis in chunks
    (:func:`init_chunk`: ``chunk_size`` when given, else derived from the
    client count and data size) with the same loop as local training:
    each chunk materializes only its own (chunk, K, d) minibatch-gradient
    stack and immediately reduces it to the (chunk, d) full gradients +
    (chunk,) variance estimates, so init memory is O(chunk·K·d) instead
    of O(m·K·d). ``mesh`` shards the client axis across devices
    (chunking within each shard) when the shard count divides m.
    """
    mesh = mesh_lib.resolve(mesh)
    chunk = init_chunk(data.x.shape[0], data.x.shape[1], var_batch_size,
                       chunk_size)
    with spans.span(spans.INIT_GRADIENTS):
        full, sig = _special_round_uploads(
            params0, data.x, data.y, apply_fn=apply_fn,
            var_batch_size=var_batch_size, chunk_size=chunk, mesh=mesh)
    gram_fn = functools.partial(ops.gram, impl=impl, stage=spans.INIT_GRAM)
    if mesh is not None:
        gram_fn = mesh_lib.replicated(gram_fn, mesh)
    with spans.span(spans.INIT_GRAM):
        gram = gram_fn(full)
    with spans.span(spans.INIT_WEIGHTS):
        delta, w = _delta_and_weights(gram, sig, data.n)
    return {"full_grads": full, "sigma_sq": sig, "delta": delta, "W": w}


@register("ucfl")
def make_ucfl(apply_fn, params0, cfg: FedConfig = FedConfig(), *,
              num_streams=None, var_batch_size=100, silhouette_key=None,
              kernel_impl=None):
    """The proposed strategy.

    num_streams: None -> full personalization (m streams, Eq. 8);
                 int k -> clustered with k streams (§IV-B);
                 "auto" -> Alg. 2 silhouette selection.

    ``cfg.w_refresh`` opts the cohort rounds into the streaming W refresh
    (see :mod:`repro.core.similarity`): the cohort's uploads re-estimate
    its Δ/σ² statistics and W is recomputed on device before the mix.

    ``cfg.async_buffer`` opts the cohort rounds into the buffered-async
    server (see :mod:`repro.federated.async_buffer`): uploads land in a
    fixed-shape pending buffer and the Eq. 8 / §IV-B mix is applied —
    staleness-discounted — only when ``flush_k`` have accumulated.
    Mutually exclusive with ``w_refresh`` for now (the refresh folds the
    barrier round's uploads; buffering them too would need a second
    (B, d) pre-params slab — recorded in ROADMAP).

    ``cfg.topology`` opts the CLUSTERED variant's cohort rounds into the
    two-tier engine (see :mod:`repro.federated.topology`): edges ship
    per-cluster partial sums, the PS normalizes once, and the centroids
    match the flat mix up to float association. Full personalization
    rejects the knob at construction (its Eq. 8 unicast mix does not
    factorize over edge partials); ``w_refresh`` composes — the fresh
    rules feed the same tiered serve.
    """
    if cfg.async_buffer is not None and cfg.w_refresh is not None:
        raise ValueError(
            "FedConfig.async_buffer and FedConfig.w_refresh cannot be "
            "combined yet: the streaming refresh consumes each barrier "
            "round's (pre, post) upload pair, which the async buffer "
            "does not retain (see ROADMAP)")
    if num_streams is None:
        topology_lib.unsupported(
            cfg.topology, "ucfl",
            "full personalization's Eq. 8 mix is per-client unicast — "
            "every receiver's row reads every cohort column, so the PS "
            "rule has no per-edge partial-sum factorization (use the "
            "clustered variant)")
    topo = topology_lib.check_composition(
        cfg.topology, f"ucfl_k{num_streams}", shard_state=cfg.shard_state,
        async_buffer=cfg.async_buffer)
    edge_arr = topo.edge_array() if topo is not None else None
    local = fedclient.make_federated_local_sgd(
        apply_fn, lr=cfg.lr, momentum=cfg.momentum, epochs=cfg.epochs,
        batch_size=cfg.batch_size, chunk_size=cfg.chunk_size, mesh=cfg.mesh,
    )
    refresh_hook = common.w_refresh_hook(cfg.w_refresh)
    acfg = cfg.async_buffer
    sops = common.StateOps(cfg.mesh, cfg.shard_state)
    layout = flat.LayoutTable.build(params0)
    slab = jax.jit(layout.slab, static_argnums=1)
    # wire schema: one delta upload either way; full personalization also
    # delta-codes its per-client downlink (each receiver's reference is
    # its own round-start row), while the clustered variant's centroid
    # groupcast stays raw — a centroid is not any receiver's old model
    if num_streams is None:
        schema = transport_lib.single_delta_schema(
            "ucfl", layout.dim,
            downlink=(transport_lib.Stream("personalized", layout.dim),))
    else:
        schema = transport_lib.single_delta_schema(
            f"ucfl_k{num_streams}", layout.dim,
            downlink=(transport_lib.Stream("centroids", layout.dim,
                                           coding="raw"),))
    # fault injection / finite guard / robust rewrite of the upload slab
    # (None when both knobs are off — the bodies keep their exact trace)
    ustage = faults_lib.upload_stage(cfg.faults, cfg.robust, schema)
    # quantized uplink (None when off — exact stage-free trace); the EF
    # accumulator slab rides the params layout, shard_state included
    tstage = transport_lib.make_wire_stage(schema, cfg.transport, "uplink")
    # per-client downlink stage (full personalization only): the served
    # row is delta-coded against the receiver's round-start model with a
    # server-side per-client EF slab
    dstage = transport_lib.make_wire_stage(schema, cfg.transport,
                                           "downlink")

    def init(key, data):
        m = data.num_clients
        if topo is not None:
            topo.check_clients(m, "ucfl")
        collab = compute_collaboration(
            apply_fn, params0, data, var_batch_size=var_batch_size,
            impl=kernel_impl, chunk_size=cfg.chunk_size, mesh=cfg.mesh,
        )
        w = collab["W"]
        labels = None
        onehot = None
        k = num_streams
        if k is not None:
            with spans.span(spans.INIT_KMEANS):
                if k == "auto":
                    kkey = (silhouette_key if silhouette_key is not None
                            else key)
                    k, _ = clustering.choose_num_streams(kkey, w,
                                                         impl=kernel_impl)
                kmeans = functools.partial(clustering.kmeans, k=int(k),
                                           impl=kernel_impl)
                if sops.mesh is not None:
                    kmeans = mesh_lib.replicated(kmeans, sops.mesh)
                res = kmeans(key, w)
                labels = res.labels
                # cluster-membership one-hots: lets the cohort round count
                # the represented clusters (downlink streams) on device
                # instead of a per-round np.unique host round-trip
                onehot = jax.nn.one_hot(labels, int(k), dtype=jnp.float32)
        with spans.span(spans.INIT_SLAB):
            stacked = slab(params0, m)
            state = {"params": stacked, "W": w, "labels": labels,
                     "cluster_onehot": onehot, "streams": k,
                     "collab": collab}
            if refresh_hook is not None:
                state["refresh"] = similarity.init_refresh_state(collab, m)
            if tstage is not None:
                state["ef"] = jnp.zeros_like(stacked)
            if dstage is not None:
                state["ef_dl"] = jnp.zeros_like(stacked)
        return state

    @functools.partial(jax.jit, static_argnames=("streams",))
    def _round(params, w, labels, x, y, key, streams):
        updated, _ = local(layout.unravel(params), x, y, key)
        if streams is None:
            mixed = aggregation.user_centric(updated, w, impl=kernel_impl)
        else:
            mixed = aggregation.clustered(updated, w, labels, streams,
                                          impl=kernel_impl)
        return layout.ravel(mixed)

    def _mix_rows(w, labels, onehot, idx, mask, safe, streams,
                  weights=None):
        # ``weights`` (buffered-async staleness discounts) replaces the
        # binary mask as the upload-column weight; None = the barrier mix
        if streams is None:
            rows = aggregation.masked_cohort_matrix(w, idx, mask, weights)
            n_streams = jnp.sum(mask)
        else:
            rows = aggregation.masked_clustered_rows(w, labels, streams,
                                                     idx, mask, weights)
            # only the clusters actually represented in the cohort put a
            # centroid model on the downlink
            oc = jnp.take(onehot, safe, axis=0) * mask[:, None]
            n_streams = jnp.sum(jnp.max(oc, axis=0) > 0)
        return rows, n_streams

    def _serve(params, pc, post, rows, idx, mask, ef_dl):
        # PS mix + downlink. dstage None (transport off, or the clustered
        # raw groupcast) keeps the fused masked mix + scatter — the exact
        # pre-schema trace. With the full variant's delta downlink the
        # mix is materialized per cohort row (same O(c·d) math, unfused),
        # delta-coded against each receiver's round-start row pc with the
        # per-client server-side EF, and scattered at the ORIGINAL slots
        # (sentinel-demoted slots drop — their receiver gets nothing, and
        # keeps both its model and its EF row).
        if dstage is None:
            return (sops.mix_scatter_flat(params, post, rows, idx, mask,
                                          impl=kernel_impl), ef_dl)
        safe = aggregation.safe_gather_index(idx, params.shape[0])
        mixed = ops.mix_aggregate(rows, post, impl=kernel_impl)
        served, efdc = dstage(pc, mixed, sops.gather(ef_dl, safe))
        ef_dl = sops.scatter(ef_dl, idx, efdc)
        return sops.scatter(params, idx, served), ef_dl

    def _tiered_serve(params, w, labels, onehot, post, idx, mask, safe):
        # Two-tier §IV-B mix. Tier 1: each edge accumulates per-cluster
        # PARTIAL sums of its own members' uploads plus the matching
        # rule-mass partials — the raw centroid rules of
        # ``masked_clustered_rows`` split by edge membership. Tier 2: the
        # PS sums the E partials and normalizes ONCE, so the centroids
        # equal the flat renormalized mix up to float association while
        # only E·k (partial, mass) aggregates transit the backhaul
        # instead of c client uploads. The alive fallback (a slot whose
        # centroid rule has no cohort mass keeps its own model) and the
        # represented-cluster stream count match the flat path exactly.
        fmask = mask.astype(w.dtype)
        lc = jnp.take(labels, safe)
        oc = jnp.take(onehot, safe, axis=0) * fmask[:, None]  # (c, k)
        cw = oc.T @ (w[safe][:, safe] * fmask[None, :])  # (k, c) raw rules
        eoh = topology_lib.edge_onehot(edge_arr, topo.num_edges, idx, mask)
        part = jnp.einsum("kc,ce,cd->ekd", cw, eoh, post)  # (E, k, d)
        pmass = jnp.einsum("kc,ce->ek", cw, eoh)  # (E, k)
        massk = jnp.sum(pmass, axis=0)  # (k,)
        cent = jnp.sum(part, axis=0) / jnp.maximum(massk, 1e-12)[:, None]
        served = jnp.where((massk > 1e-12)[lc][:, None],
                           jnp.take(cent, lc, axis=0), post)
        n_streams = jnp.sum(jnp.max(oc, axis=0) > 0)
        return sops.scatter(params, idx, served), n_streams

    @functools.partial(jax.jit, static_argnames=("streams",),
                       donate_argnums=(0, 1, 2))
    def _masked(params, ef, ef_dl, w, labels, onehot, idx, mask, x, y, key,
                streams):
        # masked gather -> cohort local SGD -> (quantized transport) ->
        # (fault/robust upload rewrite) -> masked mix + downlink serve.
        # ``ef``/``ef_dl`` are None when the owning stage is off (empty
        # pytrees — inert donation slots, exactly the stage-free trace).
        with spans.scope(spans.GATHER):
            safe = aggregation.safe_gather_index(idx, x.shape[0])
            keys = common.cohort_keys(key, x.shape[0], safe)
            pc = sops.gather(params, safe)
            xc, yc, tree = x[safe], y[safe], layout.unravel(pc)
        updated, _ = local(tree, xc, yc, None, keys=keys)
        post = layout.ravel(updated)
        if tstage is not None:
            # EF rows ride the cohort: gathered at the clamped indices,
            # scattered back at the ORIGINAL slots (a later fault/robust
            # demotion loses the upload, not the client's residual)
            post, efc = tstage(pc, post, sops.gather(ef, safe))
            ef = sops.scatter(ef, idx, efc)
        if ustage is not None:
            post, idx, mask = ustage(pc, post, idx, mask, key, x.shape[0])
            safe = aggregation.safe_gather_index(idx, x.shape[0])
        with spans.scope(spans.MIX):
            if topo is not None:
                new, n_streams = _tiered_serve(params, w, labels, onehot,
                                               post, idx, mask, safe)
                return new, ef, ef_dl, n_streams
            rows, n_streams = _mix_rows(w, labels, onehot, idx, mask, safe,
                                        streams)
            new, ef_dl = _serve(params, pc, post, rows, idx, mask, ef_dl)
        return new, ef, ef_dl, n_streams

    @functools.partial(jax.jit, static_argnames=("streams",),
                       donate_argnums=(0, 1, 2, 3))
    def _masked_refresh(params, ef, ef_dl, refresh, w, labels, onehot, idx,
                        mask, n, x, y, key, streams):
        # masked gather -> cohort local SGD -> (quantized transport) ->
        # (fault/robust upload rewrite) -> streaming W refresh from the
        # uploads -> fused masked mix + scatter with the FRESH rows. The
        # stages run FIRST so the refresh only ever folds the upload the
        # server actually decoded, with the FINAL slot arrays: the
        # dequantized post (EF keeps its drift from the raw delta
        # bounded, so quantization noise stays out of the Δ/σ²
        # statistics) and none of the demoted/Byzantine-trimmed rows (W
        # quarantines what the guard caught).
        with spans.scope(spans.GATHER):
            safe = aggregation.safe_gather_index(idx, x.shape[0])
            keys = common.cohort_keys(key, x.shape[0], safe)
            pc = sops.gather(params, safe)
            xc, yc, tree = x[safe], y[safe], layout.unravel(pc)
        updated, _ = local(tree, xc, yc, None, keys=keys)
        post = layout.ravel(updated)
        if tstage is not None:
            post, efc = tstage(pc, post, sops.gather(ef, safe))
            ef = sops.scatter(ef, idx, efc)
        if ustage is not None:
            post, idx, mask = ustage(pc, post, idx, mask, key, x.shape[0])
            safe = aggregation.safe_gather_index(idx, x.shape[0])
        # the refresh buffers are true-dim wide (they come from the
        # special round's raveled gradients); the slab's aligned tail is
        # zero on both sides, so slicing it off is value-free
        refresh, w = refresh_hook(pc[..., :layout.dim],
                                  post[..., :layout.dim], refresh, idx,
                                  mask, n)
        with spans.scope(spans.MIX):
            if topo is not None:
                # the FRESH rules feed the same tiered serve — w_refresh
                # and the two-tier engine compose without a second path
                new, n_streams = _tiered_serve(params, w, labels, onehot,
                                               post, idx, mask, safe)
                return new, ef, ef_dl, refresh, w, n_streams
            rows, n_streams = _mix_rows(w, labels, onehot, idx, mask, safe,
                                        streams)
            new, ef_dl = _serve(params, pc, post, rows, idx, mask, ef_dl)
        return new, ef, ef_dl, refresh, w, n_streams

    amasked = _amasked_jit = None
    if acfg is not None:
        flush_k = int(acfg.flush_k)
        dim = tree_count_params(params0)
        ascatter = sops.buffer_scatter()

        @functools.partial(jax.jit, static_argnames=("streams",),
                           donate_argnums=(0, 1, 2))
        def _amasked(params, ef, abuf, w, labels, onehot, idx, mask, x, y,
                     key, streams):
            # masked gather -> cohort local SGD -> buffer deposit ->
            # staleness-weighted flush (fused mix + scatter) when >= K
            # uploads are pending. ONE compiled shape covers deposit-only
            # and flush rounds (lax.cond), so the one-compilation
            # guarantee of the barrier engine carries over.
            m = x.shape[0]
            with spans.scope(spans.GATHER):
                safe = aggregation.safe_gather_index(idx, m)
                keys = common.cohort_keys(key, m, safe)
                pc = sops.gather(params, safe)
                xc, yc, tree = x[safe], y[safe], layout.unravel(pc)
            updated, _ = local(tree, xc, yc, None, keys=keys)
            post_flat = layout.ravel(updated)
            if tstage is not None:
                # the user-centric buffer holds MODELS, so the deposit is
                # the reconstructed post' = pre + dequant — exactly what
                # the wire carried plus the base the server already has
                post_flat, efc = tstage(pc, post_flat,
                                        sops.gather(ef, safe))
                ef = sops.scatter(ef, idx, efc)
            if ustage is not None:
                # rewrite the upload BEFORE it is deposited: demoted
                # slots carry the sentinel/False mask, so their junk
                # rows never enter the pending buffer
                post_flat, idx, mask = ustage(pc, post_flat, idx, mask,
                                              key, m)
                safe = aggregation.safe_gather_index(idx, m)
            with spans.scope(spans.MIX):
                # a client trains from its OWN row, untouched since the flush
                # that last wrote it — that version is the upload's base
                base_ver = jnp.take(abuf["last_sync"], safe)
                abuf = async_buffer.deposit(abuf, post_flat, idx,
                                            mask, base_ver, m,
                                            scatter=ascatter)
                flush = abuf["count"] >= flush_k
                weights = async_buffer.staleness_weights(abuf, m, acfg.alpha)
                tau = async_buffer.staleness(abuf)
                applied = abuf["count"]
                bidx = abuf["idx"]
                bvalid = async_buffer.valid_mask(abuf, m)
                bsafe = aggregation.safe_gather_index(bidx, m)

                def do_flush(params, abuf):
                    rows, n_streams = _mix_rows(w, labels, onehot, bidx,
                                                bvalid, bsafe, streams,
                                                weights)
                    new = sops.mix_scatter_flat(params, abuf["upd"],
                                                rows, bidx, bvalid,
                                                impl=kernel_impl,
                                                flat_sharded=sops.sharded)
                    return new, async_buffer.flush_reset(abuf, m), n_streams

                def no_flush(params, abuf):
                    return params, abuf, jnp.zeros((), jnp.int32)

                params, abuf, n_streams = jax.lax.cond(
                    flush, do_flush, no_flush, params, abuf)
                metrics = {**async_buffer.flush_metrics(
                    flush, applied, tau, weights, abuf["count"]),
                    "streams": n_streams}
            return params, ef, abuf, metrics

        _amasked_jit = _amasked

        def amasked(state, data, key, idx, mask):
            abuf = common.state_async_buffer(state, acfg, data.num_clients,
                                             idx.shape[0], dim, sops,
                                             schema)
            new, ef, abuf, am = _amasked(
                state["params"], state.get("ef"), abuf, state["W"],
                state["labels"], state["cluster_onehot"], idx, mask,
                data.x, data.y, key, state["streams"])
            out = dict(state, params=new, abuf=abuf)
            if ef is not None:
                out["ef"] = ef
            return out, am

    def dense(state, data, key):
        # the dense path never refreshes: cohort=None must stay bit-exact
        # with the paper's compute-W-once engine (and has no staleness)
        new = _round(state["params"], state["W"], state["labels"],
                     data.x, data.y, key, state["streams"])
        return dict(state, params=new), {
            "streams": state["streams"] or data.num_clients}

    def masked(state, data, key, idx, mask):
        if refresh_hook is None:
            new, ef, ef_dl, n_streams = _masked(
                state["params"], state.get("ef"), state.get("ef_dl"),
                state["W"], state["labels"], state["cluster_onehot"],
                idx, mask, data.x, data.y, key, state["streams"])
            out = dict(state, params=new)
            if ef is not None:
                out["ef"] = ef
            if ef_dl is not None:
                out["ef_dl"] = ef_dl
            return out, {"streams": n_streams}
        new, ef, ef_dl, refresh, w, n_streams = _masked_refresh(
            state["params"], state.get("ef"), state.get("ef_dl"),
            state["refresh"], state["W"], state["labels"],
            state["cluster_onehot"], idx, mask, data.n, data.x, data.y,
            key, state["streams"])
        out = dict(state, params=new, refresh=refresh, W=w)
        if ef is not None:
            out["ef"] = ef
        if ef_dl is not None:
            out["ef_dl"] = ef_dl
        return (out,
                {"streams": n_streams, **common.staleness_metrics(refresh)})

    scheme = "unicast" if num_streams is None else "groupcast"
    if acfg is not None:
        masked_jit = _amasked_jit
    elif refresh_hook is not None:
        masked_jit = _masked_refresh
    else:
        masked_jit = _masked
    shard_keys = ("params",)
    if tstage is not None:
        shard_keys += ("ef",)
    if dstage is not None:
        shard_keys += ("ef_dl",)
    return Strategy(
        name="ucfl" if num_streams is None else f"ucfl_k{num_streams}",
        init=init, round=common.cohort_round(
            dense, masked, masked_jit=masked_jit, mesh=cfg.mesh,
            async_fn=amasked, async_cfg=acfg, sops=sops,
            shard_keys=shard_keys, upload_stage=ustage,
            transport=cfg.transport, topology=topo),
        eval_params=lambda s: layout.unravel(s["params"]),
        comm_scheme=scheme,
        num_streams=None if num_streams in (None, "auto") else num_streams,
        skip_round=common.refresh_skip_round if refresh_hook is not None
        else None,
        injects_faults=cfg.faults is not None,
        wire_schema=schema,
    )


@register("ucfl_parallel")
def make_ucfl_parallel(apply_fn, params0, cfg: FedConfig = FedConfig(), *,
                       var_batch_size=100, kernel_impl=None):
    """§V-E upper bound: m parallel FL instances solving Eq. 4 exactly.

    Every client locally optimizes ALL m personalized models each round
    (m× compute and uplink); the PS applies Eq. 12. Serves as the
    fully-collaborative upper bound in Fig. 6.
    """
    if cfg.shard_state:
        raise NotImplementedError(
            "FedConfig.shard_state is not supported by ucfl_parallel: its "
            "(m, c) column mix reads every stream's row each round, so "
            "there is no O(c·d) row-routing to exploit (the m× cost is "
            "the point of this upper bound)")
    if cfg.faults is not None or cfg.robust is not None:
        raise NotImplementedError(
            "FedConfig.faults/robust are not supported by ucfl_parallel: "
            "the m× per-stream update stack has no single (c, d) upload "
            "slab for the fault/robust stage to rewrite — this idealized "
            "§V-E upper bound assumes honest clients by construction")
    transport_lib.unsupported(
        cfg.transport, "ucfl_parallel",
        "the m× per-stream update stack has no single (c, d) upload "
        "slab to quantize — the m× uplink cost is the point of this "
        "upper bound")
    topology_lib.unsupported(
        cfg.topology, "ucfl_parallel",
        "the §V-E upper bound mixes EVERY stream over every cohort "
        "column with the (m, c) column-sliced W — there are no per-edge "
        "partial aggregates for an edge tier to ship")
    local = fedclient.make_federated_local_sgd(
        apply_fn, lr=cfg.lr, momentum=cfg.momentum, epochs=cfg.epochs,
        batch_size=cfg.batch_size, chunk_size=cfg.chunk_size, mesh=cfg.mesh,
    )
    refresh_hook = common.w_refresh_hook(cfg.w_refresh)
    layout = flat.LayoutTable.build(params0)

    def init(key, data):
        m = data.num_clients
        collab = compute_collaboration(
            apply_fn, params0, data, var_batch_size=var_batch_size,
            impl=kernel_impl, chunk_size=cfg.chunk_size, mesh=cfg.mesh,
        )
        state = {"params": layout.slab(params0, m), "W": collab["W"]}
        if refresh_hook is not None:
            state["refresh"] = similarity.init_refresh_state(collab, m)
        return state

    @jax.jit
    def _round(params, w, x, y, key):
        m = x.shape[0]
        tree = layout.unravel(params)

        # θ_{i,j}: client j optimizes stream i's model on its local data.
        def per_stream(stream_params, skey):
            return local(
                jax.tree.map(
                    lambda p: jnp.broadcast_to(p, (m,) + p.shape), stream_params
                ),
                x, y, skey,
            )[0]

        keys = jax.random.split(key, m)
        all_updates = jax.vmap(per_stream)(tree, keys)  # leaves (i=m, j=m, ...)
        # Eq. 12: θ_i ← Σ_j w_{i,j} θ_{i,j}
        return layout.ravel(jax.tree.map(
            lambda u: jnp.einsum("ij,ij...->i...", w, u), all_updates
        ))

    def _all_updates(params, idx, mask, x, y, key):
        # Only cohort clients compute, but they still optimize ALL m stream
        # models (the defining m× cost of this upper bound).
        m = params.shape[0]
        c = idx.shape[0]
        safe = aggregation.safe_gather_index(idx, x.shape[0])
        xc, yc = x[safe], y[safe]

        def per_stream(stream_params, skey):
            return local(
                jax.tree.map(
                    lambda p: jnp.broadcast_to(p, (c,) + p.shape), stream_params
                ),
                xc, yc, None, keys=common.cohort_keys(skey, m, safe),
            )[0]

        keys = jax.random.split(key, m)
        # leaves (i=m, j=c, ...)
        return jax.vmap(per_stream)(layout.unravel(params), keys), safe

    def _masked_mix(params, w, all_updates, idx, mask):
        # every stream mixes over the cohort's uploads with masked
        # renormalized weights (pad slots carry zero weight).
        wc, alive = aggregation.masked_column_mixing(w, idx, mask)  # (m, c)
        mixed = jax.tree.map(
            lambda u: jnp.einsum("ij,ij...->i...", wc, u), all_updates
        )
        # a stream whose W row has no mass on the cohort keeps its last
        # model instead of collapsing to the zero mix
        return jnp.where(alive[:, None], layout.ravel(mixed), params)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _masked(params, w, idx, mask, x, y, key):
        all_updates, _ = _all_updates(params, idx, mask, x, y, key)
        return _masked_mix(params, w, all_updates, idx, mask)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _masked_refresh(params, refresh, w, idx, mask, n, x, y, key):
        all_updates, safe = _all_updates(params, idx, mask, x, y, key)
        # client j's own personalized trajectory is stream idx_j: use its
        # update of its OWN stream model as the gradient-proxy upload
        c = idx.shape[0]
        own = jax.tree.map(lambda u: u[safe, jnp.arange(c)], all_updates)
        pre = gather_rows(params, safe)
        refresh, w = refresh_hook(pre[..., :layout.dim],
                                  layout.ravel(own)[..., :layout.dim],
                                  refresh, idx, mask, n)
        return _masked_mix(params, w, all_updates, idx, mask), refresh, w

    def dense(state, data, key):
        new = _round(state["params"], state["W"], data.x, data.y, key)
        return dict(state, params=new), {"streams": data.num_clients}

    def masked(state, data, key, idx, mask):
        # streams stays m even under a cohort: every participant downloads
        # ALL m stream models to optimize them (the m x cost that makes
        # this the upper bound), so m distinct models hit the downlink.
        if refresh_hook is None:
            new = _masked(state["params"], state["W"], idx, mask,
                          data.x, data.y, key)
            return dict(state, params=new), {"streams": data.num_clients}
        new, refresh, w = _masked_refresh(
            state["params"], state["refresh"], state["W"], idx, mask,
            data.n, data.x, data.y, key)
        return (dict(state, params=new, refresh=refresh, W=w),
                {"streams": data.num_clients,
                 **common.staleness_metrics(refresh)})

    return Strategy(
        name="ucfl_parallel", init=init,
        round=common.cohort_round(
            dense, masked,
            masked_jit=_masked if refresh_hook is None else _masked_refresh,
            mesh=cfg.mesh, async_cfg=cfg.async_buffer),
        eval_params=lambda s: layout.unravel(s["params"]),
        comm_scheme="unicast",
        skip_round=common.refresh_skip_round if refresh_hook is not None
        else None,
    )
