"""PS-side aggregation rules (paper Eq. 1/2/8 and §IV-B clustered variant).

All rules operate on a *client-stacked* pytree (every leaf has leading axis
m) and return a stacked pytree of the same structure:

  * ``fedavg``        — Eq. 1: one convex combination, broadcast to all m.
  * ``user_centric``  — Eq. 8: θ_i ← Σ_j W[i,j] θ_j (full personalization,
                        m distinct downlink streams).
  * ``clustered``     — §IV-B: only m_t centroid rules are materialized;
                        every client in cluster C_n receives the centroid
                        mix (group-cast, m_t streams).

Partial participation (cohort) variants operate on a *cohort-stacked*
pytree (leading axis = cohort size c ≤ m) plus the sorted cohort index
array. The (m, m) mixing matrix W is sliced to the cohort's rows/columns
and **row-renormalized** so each participating client still applies a
convex combination over the uploads that actually arrived; absent clients
keep their last personalized model (the caller scatters the cohort result
back into the full stacked state):

  * ``fedavg_cohort``       — Eq. 1 restricted to the cohort, broadcast
                              back to all m (global-model semantics).
  * ``user_centric_cohort`` — Eq. 8 with W[cohort, cohort] renormalized.
  * ``clustered_cohort``    — §IV-B with centroid rules rebuilt from the
                              cohort members of each cluster.

The heavy lifting per leaf is a (rules, m) × (m, d) matmul executed by the
``mix_aggregate`` kernel (Pallas on TPU, jnp oracle on CPU).

The fixed-shape round engine uses the ``masked_*`` variants further down:
cohorts are padded to a static slot count with zero-weight masked slots,
every rule is expressed as per-slot (c, c) rows, and the mix + scatter
into the full stacked state runs as ONE fused ``masked_mix_scatter``
kernel pass over the ravel-once (c, d) update matrix.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.pytree import stacked_ravel
from repro.kernels import ops


def _mix_tree(w, stacked, *, impl=None):
    """Apply mixing matrix w (k, m) to each leaf of a client-stacked tree."""

    def leaf(x):
        m = x.shape[0]
        flat = x.reshape(m, -1)
        out = ops.mix_aggregate(w, flat, impl=impl)
        return out.reshape((w.shape[0],) + x.shape[1:])

    return jax.tree.map(leaf, stacked)


def fedavg(stacked, n, *, impl=None):
    """Eq. 1 with w_i = n_i / Σ n_j, result broadcast back to all clients."""
    m = n.shape[0]
    w = (n / jnp.sum(n)).astype(jnp.float32)[None, :]  # (1, m)
    mixed = _mix_tree(w, stacked, impl=impl)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (m,) + x.shape[1:]), mixed)


def user_centric(stacked, w, *, impl=None):
    """Eq. 8 — full per-client personalization; w is the (m, m) matrix."""
    return _mix_tree(w, stacked, impl=impl)


def clustered(stacked, w, labels, num_clusters, *, impl=None):
    """§IV-B — m_t centroid aggregation rules, group-cast to members.

    Args:
      stacked: client-stacked pytree of locally-optimized models.
      w: (m, m) user-centric mixing matrix.
      labels: (m,) int cluster assignment from K-means over rows of w.
      num_clusters: static m_t.
    Returns:
      stacked tree where client i holds the mix of its cluster centroid.
    """
    m = w.shape[0]
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=jnp.float32)  # (m, mt)
    counts = jnp.maximum(onehot.sum(axis=0), 1.0)  # (mt,)
    centroid_w = (onehot.T @ w) / counts[:, None]  # (mt, m) — centroid rules
    mixed = _mix_tree(centroid_w, stacked, impl=impl)  # (mt, ...)
    return jax.tree.map(lambda x: jnp.take(x, labels, axis=0), mixed)


def renormalize_rows(w, eps: float = 1e-12):
    """Rescale rows to sum to 1; all-zero rows stay zero (0/eps)."""
    return w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), eps)


def cohort_mixing_matrix(w, cohort):
    """Slice W to the cohort's rows/columns and renormalize rows.

    The result is (c, c) row-stochastic (up to float error): participant i
    redistributes the mass of the absent columns proportionally across the
    uploads it did receive. A degenerate row — a participant whose W mass
    lies entirely on absent clients (possible when Eq. 9 underflows the
    off-diagonals) — falls back to the identity row, i.e. that client
    keeps its own locally-updated model instead of a zeroed mix.
    """
    wc = w[cohort][:, cohort]
    s = jnp.sum(wc, axis=1, keepdims=True)
    eye = jnp.eye(wc.shape[0], dtype=wc.dtype)
    return jnp.where(s > 1e-12, wc / jnp.maximum(s, 1e-12), eye)


def cohort_column_mixing(w, cohort):
    """Column-slice W to the cohort and renormalize every row.

    Returns ``(wc, alive)``: wc is (m, c) with rows rescaled to sum to 1,
    and alive is an (m,) bool marking rows that had any mass on the cohort
    — degenerate rows (no mass) are the caller's cue to keep the previous
    model rather than apply the (meaningless) zero mix. Shares the same
    threshold/fallback semantics as :func:`cohort_mixing_matrix`.
    """
    cols = w[:, cohort]
    s = jnp.sum(cols, axis=1, keepdims=True)
    return cols / jnp.maximum(s, 1e-12), s[:, 0] > 1e-12


def fedavg_cohort(stacked_cohort, n_cohort, m, *, impl=None):
    """Eq. 1 over the cohort's uploads; new global broadcast to all m."""
    w = (n_cohort / jnp.sum(n_cohort)).astype(jnp.float32)[None, :]  # (1, c)
    mixed = _mix_tree(w, stacked_cohort, impl=impl)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (m,) + x.shape[1:]),
                        mixed)


def user_centric_cohort(stacked_cohort, w, cohort, *, impl=None):
    """Eq. 8 restricted to the cohort; returns the cohort-stacked mix."""
    return _mix_tree(cohort_mixing_matrix(w, cohort), stacked_cohort,
                     impl=impl)


def clustered_cohort(stacked_cohort, w, labels, num_clusters, cohort, *,
                     impl=None):
    """§IV-B with centroid rules rebuilt from the cohort.

    Each centroid rule sums the W rows of its *participating* members and
    is renormalized over the cohort columns (the per-cluster member-count
    divide of :func:`clustered` would cancel against the renormalization,
    so it is omitted); clusters with no sampled member produce a zero rule
    that nobody receives. A participant whose centroid rule has no mass on
    the cohort (Eq. 9 underflow onto absent clients) keeps its own
    locally-updated model, mirroring ``cohort_mixing_matrix``'s fallback.
    """
    lc = jnp.take(labels, cohort)
    onehot = jax.nn.one_hot(lc, num_clusters, dtype=jnp.float32)  # (c, mt)
    raw = onehot.T @ w[cohort][:, cohort]  # (mt, c)
    mixed = _mix_tree(renormalize_rows(raw), stacked_cohort, impl=impl)
    alive = (jnp.sum(raw, axis=1) > 1e-12)[lc]  # (c,)
    return jax.tree.map(
        lambda x, own: jnp.where(
            alive.reshape((-1,) + (1,) * (own.ndim - 1)),
            jnp.take(x, lc, axis=0), own),
        mixed, stacked_cohort)


# --------------------------------------------------------------------------
# Padded/masked fixed-shape cohort variants.
#
# The fixed-shape engine pads every cohort to a static slot count: pad
# slots carry the sentinel index m (clamped for gathers, dropped by
# scatters) and mask == False. The rules below reproduce the cohort_*
# semantics above bit-for-bit on the real slots — the pad columns are
# zeroed before the row renormalization, so the row sums (and hence every
# mixed value) match the unpadded slicing exactly — while pad rows
# produce don't-care values that the scatter never writes. Each rule is
# expressed as a per-slot (c, c) row matrix so the whole PS step runs as
# ONE fused ``masked_mix_scatter`` kernel pass over the raveled (c, d)
# updates (see :mod:`repro.kernels.masked_mix_scatter`).
# --------------------------------------------------------------------------


def safe_gather_index(idx, m):
    """Clamp padded sentinel indices for gathers (pads read row m-1)."""
    return jnp.minimum(idx, m - 1)


def masked_cohort_matrix(w, idx, mask, weights=None):
    """Fixed-shape :func:`cohort_mixing_matrix`: (c, c) with zeroed pad
    columns, row-renormalized; degenerate rows fall back to identity.

    ``weights`` optionally replaces the binary mask as the per-slot
    COLUMN weight (the buffered-async engine passes staleness discounts
    ``(1+τ)^{-α}``, zero on empty slots); the row renormalization keeps
    every row a convex combination either way, and ``weights=None`` is
    bit-identical to the mask path.
    """
    fmask = mask.astype(w.dtype) if weights is None else weights
    safe = safe_gather_index(idx, w.shape[0])
    wc = w[safe][:, safe] * fmask[None, :]
    s = jnp.sum(wc, axis=1, keepdims=True)
    eye = jnp.eye(wc.shape[0], dtype=wc.dtype)
    return jnp.where(s > 1e-12, wc / jnp.maximum(s, 1e-12), eye)


def masked_clustered_rows(w, labels, num_clusters, idx, mask, weights=None):
    """Fixed-shape :func:`clustered_cohort` as per-slot rows.

    Returns (c, c): slot i's row is its cluster's centroid rule rebuilt
    from the masked cohort (renormalized over real columns); a slot whose
    centroid rule has no mass on the cohort falls back to the identity
    row (keeps its own locally-updated model), and pad slots are
    don't-care.

    ``weights`` optionally replaces the binary mask as the per-slot
    column weight of the uploads being mixed (staleness discounts in the
    buffered-async engine). Cluster MEMBERSHIP stays mask-based — a
    stale member still belongs to its cluster; only its upload's
    contribution is discounted. ``weights=None`` is bit-identical to the
    mask path.
    """
    fmask = mask.astype(w.dtype)
    colw = fmask if weights is None else weights
    safe = safe_gather_index(idx, w.shape[0])
    lc = jnp.take(labels, safe)
    onehot = jax.nn.one_hot(lc, num_clusters, dtype=w.dtype) * fmask[:, None]
    raw = onehot.T @ (w[safe][:, safe] * colw[None, :])  # (mt, c)
    rules = renormalize_rows(raw)
    alive = (jnp.sum(raw, axis=1) > 1e-12)[lc]  # (c,)
    eye = jnp.eye(safe.shape[0], dtype=w.dtype)
    return jnp.where(alive[:, None], jnp.take(rules, lc, axis=0), eye)


def masked_group_rows(assignment_c, n_c, mask):
    """Fixed-shape per-group FedAvg rows (CFL/Oracle cohort variant).

    assignment_c/n_c are the (c,) cohort-slot cluster ids and dataset
    sizes (pad slots: clamped-gather values, zeroed by the mask).
    """
    fmask = mask.astype(jnp.float32)
    same = (assignment_c[:, None] == assignment_c[None, :]).astype(jnp.float32)
    w = same * n_c.astype(jnp.float32)[None, :] * fmask[None, :]
    s = jnp.sum(w, axis=1, keepdims=True)
    eye = jnp.eye(w.shape[0], dtype=w.dtype)
    return jnp.where(s > 1e-12, w / jnp.maximum(s, 1e-12), eye)


def masked_fedavg_weights(n_c, mask, weights=None):
    """Fixed-shape Eq. 1 weights over the cohort: (1, c), pad slots 0.

    An all-masked cohort yields all-zero weights (0/eps) rather than NaN;
    ``fedavg_masked_mix`` uses that to fall back to the previous model.
    ``weights`` optionally replaces the binary mask (staleness discounts
    in the buffered-async engine, zero on empty slots); ``None`` is
    bit-identical to the mask path.
    """
    wn = n_c.astype(jnp.float32) * (
        mask.astype(jnp.float32) if weights is None else weights)
    return (wn / jnp.maximum(jnp.sum(wn), 1e-12))[None, :]


def masked_column_mixing(w, idx, mask):
    """Fixed-shape :func:`cohort_column_mixing` for the §V-E upper bound:
    (m, c) row-renormalized over real cohort columns, plus the (m,) alive
    marker for degenerate rows."""
    fmask = mask.astype(w.dtype)
    safe = safe_gather_index(idx, w.shape[0])
    cols = w[:, safe] * fmask[None, :]
    s = jnp.sum(cols, axis=1, keepdims=True)
    return cols / jnp.maximum(s, 1e-12), s[:, 0] > 1e-12


def masked_ewma_rows(buf, obs, idx, mask, alpha):
    """EWMA-fold per-slot observations into rows of a running buffer.

    ``buf`` is (m, ...) and ``obs`` is (c, ...): real slot i rewrites row
    ``idx[i]`` as ``(1−α)·buf + α·obs``; pad slots (sentinel index m,
    dropped by the scatter; mask False, blend suppressed) leave the
    buffer untouched. Used by the streaming W refresh for the (m, d)
    gradient-proxy buffer and the (m,) σ² buffer.
    """
    safe = safe_gather_index(idx, buf.shape[0])
    prev = jnp.take(buf, safe, axis=0)
    fmask = mask.reshape((-1,) + (1,) * (obs.ndim - 1)).astype(buf.dtype)
    blended = prev + fmask * alpha * (obs.astype(buf.dtype) - prev)
    return buf.at[idx].set(blended, mode="drop")


def masked_unit_ewma_rows(buf, obs, idx, mask, alpha, eps=1e-12):
    """:func:`masked_ewma_rows` re-projected onto the unit sphere.

    The streaming refresh keeps its (m, d) gradient-DIRECTION buffer
    unit-norm (the scale-free statistic space, see
    :mod:`repro.core.similarity`); a plain EWMA of two unit vectors has
    norm < 1, which would shrink every subsequent distance against the
    blended row, so the blend is renormalized before the scatter.
    """
    safe = safe_gather_index(idx, buf.shape[0])
    prev = jnp.take(buf, safe, axis=0)
    blended = prev + alpha * (obs.astype(buf.dtype) - prev)
    blended = blended / jnp.maximum(
        jnp.linalg.norm(blended, axis=-1, keepdims=True), eps)
    rows = jnp.where(mask[:, None], blended, prev)
    return buf.at[idx].set(rows, mode="drop")


def masked_delta_rows(delta, grads, idx, mask):
    """Refresh the observed clients' rows AND columns of the Δ buffer.

    Recomputes ``Δ[idx_i, j] = ‖grads[idx_i] − grads[j]‖²`` for every
    real slot against the full (already refreshed) gradient buffer and
    scatters it into both the rows and the (symmetric) columns; entries
    between two absent clients keep their previous value. Both of a
    cohort pair's entries derive from the same matmul, so the refreshed
    Δ stays symmetric with a zero diagonal up to matmul round-off (the
    expansion is clamped at 0; Eq. 9 needs no exact symmetry). Pad slots
    are dropped by the sentinel-index scatter and masked out of the row
    values.
    """
    m = delta.shape[0]
    safe = safe_gather_index(idx, m)
    g = jnp.take(grads, safe, axis=0).astype(jnp.float32)  # (c, d)
    gm = grads.astype(jnp.float32)  # (m, d)
    sq = jnp.sum(g * g, axis=-1)[:, None] + \
        jnp.sum(gm * gm, axis=-1)[None, :] - 2.0 * (g @ gm.T)
    rows = jnp.maximum(sq, 0.0)  # (c, m); clamp matmul round-off
    prev = jnp.take(delta, safe, axis=0)
    rows = jnp.where(mask[:, None], rows, prev)
    out = delta.at[idx].set(rows, mode="drop")       # observed rows
    return out.at[:, idx].set(rows.T, mode="drop")   # symmetric columns


def staleness_update(stale, idx, mask):
    """Advance the per-client staleness counters by one cohort round.

    Every client's counter (rounds since its Δ/σ² stats were observed)
    increments; the real cohort slots then reset to 0. Pad slots are
    dropped by the sentinel scatter and masked out of the reset.
    """
    bumped = stale + 1
    safe = safe_gather_index(idx, stale.shape[0])
    reset = jnp.where(mask, 0, jnp.take(bumped, safe))
    return bumped.at[idx].set(reset, mode="drop")


def cohort_gather(full, safe, *, impl=None):
    """Round-start cohort gather ``full[safe]``.

    A single-leaf stacked tree gathers through the HBM-resident row
    loop (:func:`repro.kernels.ops.cohort_gather`) on the zero-copy
    (m, d) flat view — ``full`` never streams through VMEM, so traffic
    is O(c·d) at any m. Multi-leaf trees fall back to the
    per-leaf ``jnp.take`` (:func:`repro.core.pytree.gather_rows`) —
    raveling the full state would copy it. ``safe`` must be pre-clamped
    (:func:`safe_gather_index`); semantics are bit-identical to
    ``gather_rows``.
    """
    leaves, treedef = jax.tree.flatten(full)
    if len(leaves) == 1:
        leaf = leaves[0]
        flat = leaf.reshape(leaf.shape[0], -1)  # zero-copy view
        out = ops.cohort_gather(flat, safe, impl=impl)
        return jax.tree.unflatten(
            treedef, [out.reshape((safe.shape[0],) + leaf.shape[1:])])
    return jax.tree.map(lambda x: jnp.take(x, safe, axis=0), full)


def mix_scatter(full, cohort_updated, rows, idx, mask, *, impl=None):
    """Apply per-slot mixing rows and scatter into the full stacked state.

    The cohort-stacked update tree is raveled ONCE to a (c, d) matrix
    and the whole PS mix runs as the fully fused ``masked_mix_scatter``
    kernel pass — mix + masked row scatter over a zero-copy (m, d)
    reshape view, with the pallas path aliasing the state buffer.

    The stacked state must be a single leaf: the slab engine
    (:class:`repro.core.flat.LayoutTable`) is the state contract, and
    every strategy ravels multi-leaf models into one (m, d_aligned)
    matrix at construction. The old per-leaf scatter fallback is gone —
    a multi-leaf ``full`` here means a caller bypassed the layout table,
    which is an error, not a slow path.

    Pad slots rely on the sentinel-index contract: the scatter drops
    out-of-range rows, so ``mask`` must be False exactly where ``idx``
    is the sentinel m (guaranteed by ``participation.as_cohort``).
    """
    return mix_scatter_flat(full, stacked_ravel(cohort_updated), rows,
                            idx, mask, impl=impl)


def mix_scatter_flat(full, flat_c, rows, idx, mask, *, impl=None):
    """:func:`mix_scatter` for an ALREADY-raveled (c, d) update matrix.

    The buffered-async flush stores pending uploads as raveled rows, so
    there is no cohort-stacked tree to ravel: the single-leaf state takes
    the same fused ``masked_mix_scatter`` kernel pass. ``flat_c`` wider
    than the state's flat dim (a true-dim cohort ravel against a
    128-aligned slab never happens, but the async buffer may allocate
    beyond ``aligned_dim``) is sliced back — the tail columns are the
    deposit-time zero padding. Sentinel/mask semantics are identical to
    :func:`mix_scatter`; multi-leaf stacked state raises (see there).
    """
    leaves, treedef = jax.tree.flatten(full)
    if len(leaves) != 1:
        raise ValueError(
            "mix_scatter: multi-leaf stacked state is no longer supported "
            "on the mix path — the slab engine (repro.core.flat."
            "LayoutTable) is the state contract; ravel the state to one "
            f"(m, dim_aligned) matrix (got {len(leaves)} leaves)")
    leaf = leaves[0]
    d = leaf.size // leaf.shape[0]
    if flat_c.shape[1] > d:
        flat_c = flat_c[:, :d]
    flat = leaf.reshape(leaf.shape[0], -1)  # zero-copy view
    out = ops.masked_mix_scatter(rows, flat_c, idx, mask, flat, impl=impl)
    return jax.tree.unflatten(treedef, [out.reshape(leaf.shape)])


def centroid_rules(w, labels, num_clusters):
    """The (m_t, m) centroid mixing rows (the downlink streams)."""
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=jnp.float32)
    counts = jnp.maximum(onehot.sum(axis=0), 1.0)
    return (onehot.T @ w) / counts[:, None]


# --------------------------------------------------------------------------
# Byzantine-robust masked rules.
#
# Each rule is a fixed-shape rewrite of the masked upload stage
# ``(flat_c, idx, mask) -> (flat_c', idx', mask')`` applied BEFORE the
# (c, c)-row mix: value rules (trimmed mean / median / norm clip)
# sanitize the (c, d) upload slab in place, selection rules (Krum /
# multi-Krum) demote deselected slots to masked pad slots (mask False,
# sentinel index — the exact contract the finite guard and the
# sentinel-drop scatter already obey); trimmed mean does both — it
# winsorizes surviving values AND demotes rows that are coordinate
# outliers in a supermajority of coordinates (a clamped attacker row
# would otherwise keep its full mixing mass). Because the rewrite
# happens on
# the replicated cohort slab and the downstream rules are the existing
# masked (c, c) rows, the whole PS step keeps its single fused
# ``masked_mix_scatter`` launch, composes with staleness weights /
# ``w_refresh`` unchanged, and works under ``shard_state`` at O(c·d)
# server cost. A rule at its neutral parameter (``trim_k=0``,
# ``clip=inf``, ``multi_krum`` selecting every real slot) is a bit-exact
# pass-through. All rules assume a FINITE slab — run
# :func:`repro.federated.faults.finite_guard` first.
# --------------------------------------------------------------------------

_BIG = 1e30  # finite stand-in for +inf (inf * 0 = NaN would poison sorts)


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """Byzantine-robust aggregation policy (``FedConfig.robust``).

    Attributes:
      rule: ``trimmed_mean`` | ``median`` | ``norm_clip`` | ``krum`` |
        ``multi_krum``.
      trim_k: coordinates trimmed (winsorized) from EACH tail
        (trimmed_mean); rows outside the inlier range in ≥ 75% of
        coordinates are demoted to masked pad slots. 0 is a bit-exact
        no-op.
      clip: deviation-norm ceiling (norm_clip); rows are shrunk toward
        the masked cohort mean. ``inf`` is a bit-exact no-op.
      f: assumed Byzantine count entering the Krum score (sum over the
        ``c_real − f − 2`` nearest neighbors).
      q: slots multi_krum keeps (``krum`` forces 1; ``None`` under
        multi_krum keeps ``c_real − f``). ``q >= c_real`` keeps every
        real slot — a bit-exact no-op.
    """

    rule: str = "trimmed_mean"
    trim_k: int = 1
    clip: float = math.inf
    f: int = 1
    q: int | None = None

    _RULES = ("trimmed_mean", "median", "norm_clip", "krum", "multi_krum")

    def __post_init__(self):
        if self.rule not in self._RULES:
            raise ValueError(f"unknown robust rule {self.rule!r} "
                             f"(expected one of {self._RULES})")
        if self.trim_k < 0:
            raise ValueError(f"trim_k must be >= 0, got {self.trim_k}")
        if self.clip <= 0:
            raise ValueError(f"clip must be > 0, got {self.clip}")


def masked_trimmed_mean(flat_c, mask, trim_k: int):
    """Coordinate-wise winsorized trimmed mean over the real cohort rows.

    For every coordinate, the ``trim_eff = min(trim_k, (c_real−1)//2)``
    smallest and largest real values are treated as outliers and CLAMPED
    to the surviving inlier range ``[lo, hi]`` (winsorization) instead
    of being replaced by a cross-client mean: a clamped attacker value
    cannot leave the honest coordinate range, while an honest extreme
    keeps (a clipped version of) its own signal rather than being
    averaged away — which matters under user-centric W mixing, where
    every trimmed honest value would otherwise dilute that client's
    personalization. In-range values pass through untouched (``clip`` is
    the identity for them), so ``trim_k=0`` is bit-exact. Masked rows
    are left as-is (their mix weight is already zero).
    Permutation-equivariant over rows by construction (order statistics).
    """
    if trim_k == 0:
        return flat_c
    lo, hi, fmask = _winsor_bounds(flat_c, mask, trim_k)
    return jnp.where(fmask, jnp.clip(flat_c, lo, hi), flat_c)


def _winsor_bounds(flat_c, mask, trim_k: int):
    """Per-coordinate inlier range after trimming ``trim_eff`` per tail.

    Returns ``(lo, hi, fmask)`` with lo/hi of shape (1, d) — the
    ``trim_eff``-th and ``(n_real−1−trim_eff)``-th order statistics of
    the real rows — and the (c, 1) bool row mask.
    """
    c = flat_c.shape[0]
    fmask = mask[:, None]
    n_real = jnp.sum(mask.astype(jnp.int32))
    trim_eff = jnp.minimum(trim_k, jnp.maximum(n_real - 1, 0) // 2)
    # ascending sort with masked rows pushed past every real value
    vals = jnp.where(fmask, flat_c, _BIG)
    svals = jnp.sort(vals, axis=0)
    lo_i = jnp.clip(trim_eff, 0, c - 1)
    hi_i = jnp.clip(n_real - 1 - trim_eff, 0, c - 1)
    take = lambda i: jnp.take_along_axis(  # noqa: E731 — tiny local helper
        svals, jnp.full((1, flat_c.shape[1]), i, jnp.int32), axis=0)
    return take(lo_i), take(hi_i), fmask


def trimmed_outlier_rows(flat_c, mask, trim_k: int, frac: float = 0.75):
    """Real rows that sit OUTSIDE the winsorization inlier range in at
    least ``frac`` of coordinates — i.e. rows that are coordinate-wise
    outliers almost everywhere, which no honest update is (honest rows
    land in the trimmed tails of scattered coordinates, a Byzantine
    sign-flip/scaled-noise row lands there in essentially all of them).

    Winsorization alone cannot defend a W-weighted mix: the clamped
    attacker row still carries its full mixing mass, now pointed at the
    boundary of the honest range — a systematic per-coordinate bias.
    Demoting supermajority-outlier rows (the caller flips them to masked
    pad slots, the same sentinel contract as drops/finite-guard) removes
    that mass entirely; the W renormalization over survivors does the
    rest. Returns a (c,) bool demote mask (False for masked rows).
    """
    lo, hi, fmask = _winsor_bounds(flat_c, mask, trim_k)
    out = fmask & ((flat_c < lo) | (flat_c > hi))
    d = max(flat_c.shape[1], 1)
    out_frac = jnp.sum(out.astype(jnp.float32), axis=1) / d
    return mask & (out_frac >= frac)


def masked_median_rows(flat_c, mask):
    """Replace every real row with the coordinate-wise masked median.

    The median of ``c_real`` values averages the two central order
    statistics for even counts. Any convex (c, c)-row mix of identical
    rows returns the median itself, so the downstream rule — FedAvg
    weights, user-centric W, clustered centroids — degenerates to the
    coordinate-median aggregate, which ≤ ⌊(c_real−1)/2⌋ arbitrary rows
    cannot move outside the honest rows' coordinate ranges (the
    breakdown property the tests pin).
    """
    c = flat_c.shape[0]
    n_real = jnp.sum(mask.astype(jnp.int32))
    svals = jnp.sort(jnp.where(mask[:, None], flat_c, _BIG), axis=0)
    k_lo = jnp.clip((n_real - 1) // 2, 0, c - 1)
    k_hi = jnp.clip(n_real // 2, 0, c - 1)
    take = lambda i: jnp.take_along_axis(  # noqa: E731
        svals, jnp.full((1, flat_c.shape[1]), i, jnp.int32), axis=0)
    med = 0.5 * (take(k_lo) + take(k_hi))
    return jnp.where(mask[:, None], med, flat_c)


def masked_norm_clip(flat_c, mask, clip: float):
    """Clip each real row's deviation from the masked cohort mean.

    Rows whose deviation norm already fits under ``clip`` pass through
    via ``jnp.where`` (bit-exact — the clip idempotence/no-op property),
    outliers are shrunk radially onto the ``clip`` sphere around the
    mean. ``clip=inf`` never fires (`norm <= inf` is always true).
    """
    fmask = mask[:, None].astype(flat_c.dtype)
    cnt = jnp.maximum(jnp.sum(fmask), 1.0)
    mu = jnp.sum(flat_c * fmask, axis=0, keepdims=True) / cnt
    dev = flat_c - mu
    norm = jnp.sqrt(jnp.sum(dev * dev, axis=1, keepdims=True))
    scaled = mu + dev * (clip / jnp.maximum(norm, 1e-12))
    keep = (norm <= clip) | ~mask[:, None]
    return jnp.where(keep, flat_c, scaled)


def krum_scores(flat_c, mask, f: int):
    """Krum scores over the masked cohort (lower = more central).

    score_i = sum of slot i's ``max(c_real − f − 2, 1)`` smallest
    squared distances to the OTHER real slots; masked slots (and pairs
    touching them) score ``_BIG`` so they never outrank a real slot.
    """
    c = flat_c.shape[0]
    x = jnp.where(mask[:, None], flat_c, 0.0).astype(jnp.float32)
    sq = jnp.sum(x * x, axis=1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0)
    pair_ok = mask[:, None] & mask[None, :] & ~jnp.eye(c, dtype=bool)
    d2 = jnp.where(pair_ok, d2, _BIG)
    sd = jnp.sort(d2, axis=1)  # ascending; invalid pairs at the top
    n_real = jnp.sum(mask.astype(jnp.int32))
    k = jnp.clip(n_real - f - 2, 1, c - 1)
    csum = jnp.cumsum(sd, axis=1)
    score = jnp.take_along_axis(
        csum, jnp.full((c, 1), 0, jnp.int32) + (k - 1), axis=1)[:, 0]
    return jnp.where(mask, score, _BIG)


def masked_krum_select(flat_c, idx, mask, m: int, f: int,
                       q: int | None = None):
    """(multi-)Krum selection as a cohort-slot rewrite.

    Keeps the ``q`` lowest-scoring real slots (``q=None`` keeps
    ``c_real − f``; ``q=1`` is classic Krum) and demotes the rest to
    masked pad slots — mask False, sentinel index ``m`` — exactly like
    the finite guard, so deselected clients keep their previous model
    and every downstream rule/scatter composes unchanged. When the keep
    count covers every real slot the rewrite is bit-exact (``mask`` and
    ``idx`` come back unchanged). Returns ``(idx', mask')``.
    """
    score = krum_scores(flat_c, mask, f)
    n_real = jnp.sum(mask.astype(jnp.int32))
    keep_n = (jnp.maximum(n_real - f, 1) if q is None
              else jnp.clip(q, 1, flat_c.shape[0]))
    # rank via double argsort: deterministic under ties
    rank = jnp.argsort(jnp.argsort(score))
    selected = mask & (rank < keep_n)
    return jnp.where(selected, idx, m), selected


def robust_stage(cfg: RobustConfig | None):
    """Build the robust upload rewrite, or ``None`` when the knob is off.

    Returns a traceable ``stage(flat_c, idx, mask, m) ->
    (flat_c', idx', mask')`` over the replicated (c, d) upload slab.
    """
    if cfg is None:
        return None

    if cfg.rule == "trimmed_mean":
        def stage(flat_c, idx, mask, m):
            out = masked_trimmed_mean(flat_c, mask, cfg.trim_k)
            if cfg.trim_k == 0:  # neutral knob: bit-exact pass-through
                return out, idx, mask
            keep = mask & ~trimmed_outlier_rows(flat_c, mask, cfg.trim_k)
            return out, jnp.where(keep, idx, m), keep
    elif cfg.rule == "median":
        def stage(flat_c, idx, mask, m):
            return masked_median_rows(flat_c, mask), idx, mask
    elif cfg.rule == "norm_clip":
        def stage(flat_c, idx, mask, m):
            return masked_norm_clip(flat_c, mask, cfg.clip), idx, mask
    else:  # krum / multi_krum
        q = 1 if cfg.rule == "krum" else cfg.q

        def stage(flat_c, idx, mask, m):
            idx, mask = masked_krum_select(flat_c, idx, mask, m, cfg.f, q)
            return flat_c, idx, mask

    return stage
