"""Jit'd dispatch layer over the Pallas kernels.

Every op picks an implementation:
  * ``impl="pallas"``      — compiled TPU kernel (requires a TPU backend),
  * ``impl="interpret"``   — Pallas interpret mode (CPU, for validation),
  * ``impl="ref"``         — pure-jnp oracle from :mod:`repro.kernels.ref`,
  * ``impl=None`` (auto)   — the ``REPRO_KERNEL_IMPL`` env var when set
    (CI uses it to force interpret mode on CPU), else pallas on TPU and
    ref elsewhere.

The cohort mix-scatter additionally picks a *variant*: the VMEM slab
kernel or the HBM-resident row path (:mod:`repro.kernels.cohort_rows`,
plain XLA that moves only the cohort's rows). Auto picks the slab while
it fits the VMEM budget (``masked_mix_scatter.slab_fits``) and falls over
to HBM-resident past it; the suffixes ``_slab`` / ``_hbm`` (e.g.
``impl="interpret_hbm"`` or ``REPRO_KERNEL_IMPL=pallas_hbm``) force
either side.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import cohort_rows, ref
from repro.kernels.masked_mix_scatter import (
    masked_mix_scatter_pallas, slab_fits,
)
from repro.kernels.mix_aggregate import mix_aggregate_pallas
from repro.kernels.pairwise_delta import gram_pallas
from repro.kernels.kmeans_assign import kmeans_assign_pallas


ALIGN = 128  # TPU lane width: the last-dim tile every kernel wants


def aligned_dim(d: int) -> int:
    """Round a flat feature dim up to the 128 lane multiple.

    Flat stacked state created at this width (the async upload buffer,
    toy flat models) always takes the aliased zero-copy kernel path —
    ``masked_mix_scatter_pallas`` never has to zero-pad the state into
    an aligned buffer (see ``masked_mix_scatter.padding_copy_needed``).
    """
    return -(-int(d) // ALIGN) * ALIGN


def _auto_impl(impl):
    if impl is not None:
        return impl
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _split_variant(impl):
    """Split ``impl`` into (base, variant): ``"interpret_hbm"`` ->
    ``("interpret", "hbm")``; no suffix -> variant None (auto)."""
    for suffix in ("_hbm", "_slab"):
        if impl.endswith(suffix):
            return impl[: -len(suffix)], suffix[1:]
    return impl, None


def mix_aggregate(w, theta, *, impl=None, block_d=None):
    """out[i] = sum_j w[i,j] theta[j];  w (k, m), theta (m, d) -> (k, d)."""
    impl, _ = _split_variant(_auto_impl(impl))
    if impl == "ref":
        return ref.mix_aggregate(w, theta)
    kwargs = {} if block_d is None else {"block_d": block_d}
    return mix_aggregate_pallas(w, theta, interpret=(impl == "interpret"), **kwargs)


def masked_mix_scatter(w, theta, idx, mask, full, *, impl=None, block_d=None):
    """Fused cohort mix + scatter: ``full[idx[i]] = (w @ theta)[i]`` where
    ``mask[i]``; pad slots (sentinel index, mask 0) are dropped.

    w (c, c); theta (c, d); idx/mask (c,); full (m, d) -> (m, d). The
    pallas path donates/aliases ``full`` so the stacked state is updated
    in place — callers must not reuse the input buffer afterwards.

    Variant selection (``_slab``/``_hbm`` impl suffix, else auto): the
    VMEM-slab kernel while ``slab_fits(m, c)``, the HBM-resident row
    path past that bound — O(c·d) traffic at any m.
    """
    if theta.shape[1] != full.shape[1]:
        raise ValueError(
            f"masked_mix_scatter: upload width {theta.shape[1]} != state "
            f"width {full.shape[1]} — the layout table and the slab "
            "disagree (state rebuilt from a different params template?)")
    impl = _auto_impl(impl)
    if impl == "ref":
        return ref.masked_mix_scatter(w, theta, idx, mask, full)
    impl, variant = _split_variant(impl)
    if variant is None:
        variant = "slab" if slab_fits(full.shape[0], w.shape[0]) else "hbm"
    if variant == "hbm":
        return cohort_rows.mix_scatter_rows(w, theta, idx, mask, full)
    kwargs = {} if block_d is None else {"block_d": block_d}
    return masked_mix_scatter_pallas(w, theta, idx, mask, full,
                                     interpret=(impl == "interpret"),
                                     **kwargs)


def cohort_gather(full, idx, *, impl=None):
    """Round-start cohort gather: ``out[i] = full[min(idx[i], m-1)]``.

    Every impl but ref takes the HBM-resident row loop
    (:func:`repro.kernels.cohort_rows.gather_rows`) — traffic O(c·d) at
    any m. ref is ``jnp.take`` on the clamped indices, which XLA's TPU
    gather lowers with a temporary the size of ``full``; the two are
    bit-identical.
    """
    impl, _ = _split_variant(_auto_impl(impl))
    if impl == "ref":
        return ref.cohort_gather(full, idx)
    return cohort_rows.gather_rows(full, idx)


def gram(g, *, impl=None, block_d=None, stage=None):
    """Gram matrix ``g g^T`` of (m, d) rows -> (m, m) f32. ``stage`` names
    the kernel program's device stage."""
    impl, _ = _split_variant(_auto_impl(impl))
    if impl == "ref":
        return ref.gram(g)
    kwargs = {} if block_d is None else {"block_d": block_d}
    return gram_pallas(g, interpret=(impl == "interpret"), stage=stage,
                       **kwargs)


def delta_from_gram(gr):
    """Pairwise squared distances ``G_ii + G_jj - 2 G_ij`` from a Gram
    matrix, clamped at 0 (round-off can push a distance below it)."""
    sq = jnp.diag(gr)
    return jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gr, 0.0)


def pairwise_delta(g, *, impl=None, block_d=None):
    """Pairwise squared distances between rows of g (m, d) -> (m, m)."""
    return delta_from_gram(gram(g, impl=impl, block_d=block_d))


def kmeans_assign(points, centroids, *, impl=None):
    """Nearest-centroid assignment -> (labels (m,), sqdist (m,))."""
    impl, _ = _split_variant(_auto_impl(impl))
    if impl == "ref":
        return ref.kmeans_assign(points, centroids)
    return kmeans_assign_pallas(points, centroids, interpret=(impl == "interpret"))


def flash_attention(q, k, v, *, impl=None, **kw):
    """Block-wise fused attention (B, H, S, Dh); see kernels.flash_attention.

    ref path materializes the S×S matrix (what the kernel exists to avoid)
    — used on CPU where Mosaic is unavailable.
    """
    from repro.kernels import flash_attention as fa

    impl, _ = _split_variant(_auto_impl(impl))
    if impl == "ref":
        import jax.numpy as _jnp

        g = q.shape[1] // k.shape[1]
        kx = _jnp.repeat(k, g, axis=1)
        vx = _jnp.repeat(v, g, axis=1)
        s = _jnp.einsum("bhqd,bhkd->bhqk", q.astype(_jnp.float32),
                        kx.astype(_jnp.float32)) * q.shape[-1] ** -0.5
        cap = kw.get("softcap")
        if cap:
            s = cap * _jnp.tanh(s / cap)
        rows = _jnp.arange(q.shape[2])[:, None]
        cols = _jnp.arange(k.shape[2])[None, :]
        mask = _jnp.ones((q.shape[2], k.shape[2]), bool)
        if kw.get("causal", True):
            mask &= cols <= rows
        if kw.get("window"):
            mask &= cols > rows - kw["window"]
        s = _jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _jnp.einsum("bhqk,bhkd->bhqd", p,
                           vx.astype(_jnp.float32)).astype(q.dtype)
    return fa.flash_attention(q, k, v, interpret=(impl == "interpret"), **kw)
