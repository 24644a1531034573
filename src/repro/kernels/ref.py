"""Pure-jnp oracles for the PS-side Pallas kernels.

These are the semantic ground truth: every Pallas kernel in this package is
tested (shape/dtype sweeps, interpret mode) against these functions, and the
CPU execution path of :mod:`repro.kernels.ops` dispatches here.
"""
from __future__ import annotations

import jax.numpy as jnp


def mix_aggregate(w, theta):
    """User-centric mixing: ``out[i] = sum_j w[i, j] * theta[j]``.

    Args:
      w: (m, m) or (k, m) float mixing matrix (rows = aggregation rules).
      theta: (m, d) stacked flat client models.
    Returns:
      (k, d) mixed models, in ``theta.dtype``.
    """
    out = jnp.einsum("kj,jd->kd", w.astype(jnp.float32), theta.astype(jnp.float32))
    return out.astype(theta.dtype)


def cohort_gather(full, idx):
    """Gather cohort rows (oracle for the HBM-resident row gather).

    ``out[i] = full[min(idx[i], m - 1)]`` — pad slots (sentinel index
    >= m) read the clamped last row, exactly the ``safe_gather_index``
    convention of the masked engine.

    Args:
      full: (m, d) stacked client state.
      idx: (c,) int cohort indices (sentinel m on pad slots).
    Returns:
      (c, d) cohort-stacked rows, in ``full.dtype``.
    """
    safe = jnp.minimum(idx, full.shape[0] - 1)
    return jnp.take(full, safe, axis=0)


def masked_mix_scatter(w, theta, idx, mask, full):
    """Fused masked cohort mix + scatter (oracle for the Pallas kernel).

    ``out = full`` with ``out[idx[i]] = (w @ theta)[i]`` for every cohort
    slot whose ``mask[i]`` is set. Pad slots (mask 0) carry an
    out-of-range sentinel index and are dropped by the scatter; a pad
    slot with an in-bounds index writes the row's previous value back
    (identity), so either pad convention is safe.

    Args:
      w: (c, c) float mixing matrix (row i = slot i's aggregation rule;
        pad columns must be zero).
      theta: (c, d) cohort-stacked flat updates.
      idx: (c,) int target rows in ``full``.
      mask: (c,) bool, True on real cohort slots.
      full: (m, d) stacked client state.
    Returns:
      (m, d) updated state, in ``full.dtype``.
    """
    mixed = jnp.einsum(
        "ij,jd->id", w.astype(jnp.float32), theta.astype(jnp.float32)
    ).astype(full.dtype)
    safe = jnp.minimum(idx, full.shape[0] - 1)
    upd = jnp.where(mask[:, None], mixed, jnp.take(full, safe, axis=0))
    return full.at[idx].set(upd, mode="drop")


def gram(g):
    """Gram matrix ``G G^T`` of (m, d) stacked gradients, f32 accumulate."""
    g32 = g.astype(jnp.float32)
    return g32 @ g32.T


def pairwise_delta(g):
    """Pairwise squared L2 distances between rows of ``g`` (m, d) -> (m, m)."""
    gr = gram(g)
    sq = jnp.diag(gr)
    d = sq[:, None] + sq[None, :] - 2.0 * gr
    return jnp.maximum(d, 0.0)


def kmeans_assign(points, centroids):
    """Nearest-centroid assignment.

    Args:
      points: (m, f); centroids: (k, f).
    Returns:
      labels (m,) int32, sq_dists (m,) f32 to the chosen centroid.
    """
    p = points.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d = (
        jnp.sum(p * p, axis=1)[:, None]
        + jnp.sum(c * c, axis=1)[None, :]
        - 2.0 * (p @ c.T)
    )
    d = jnp.maximum(d, 0.0)
    labels = jnp.argmin(d, axis=1).astype(jnp.int32)
    return labels, jnp.min(d, axis=1)
