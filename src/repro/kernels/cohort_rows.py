"""HBM-resident cohort row movement: O(c·d) traffic at any m, via XLA.

Past the VMEM-slab kernel's bound (``masked_mix_scatter.slab_fits``) the
(m, d) stacked state must stay in HBM and only the c cohort rows may
move. On a TPU an (m, d) f32 array is laid out in (8, 128) tiles, and
Mosaic refuses a DMA of one row of it ("Slice shape along dimension 0
must be aligned to tiling (8)"); storing the state in another shape makes
XLA insert a relayout copy of the whole state. Compiled for a v5e at
m=16,384, c=64, d=45,312, XLA's own ops behave as follows:

  * ``jnp.take`` (XLA gather) of c rows keeps a temporary the size of
    the whole state, so the gather is a loop of one-row
    ``dynamic_slice``\\ s instead: its temporaries are O(c·d);
  * the drop-mode row scatter ``full.at[idx].set(rows, mode="drop")`` on
    the donated state writes in place, with O(c·d) temporaries.

Slot contract (owned by :mod:`repro.federated.participation`): pad slots
carry an out-of-range sentinel index (>= m) and ``mask[i] == 0``. The
gather reads the clamped row m-1 for them (exactly ``ref.cohort_gather``)
and the scatter drops them. The oracles are :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _check(cond: bool, msg: str):
    # ValueError (not assert): shape contracts must survive python -O
    if not cond:
        raise ValueError(msg)


@jax.jit
def gather_rows(full, idx):
    """``out[i] = full[min(idx[i], m-1)]``: (m, d), (c,) -> (c, d)."""
    _check(full.ndim == 2, f"full must be (m, d), got {full.shape}")
    _check(idx.ndim == 1, f"idx must be (c,), got {idx.shape}")
    m, d = full.shape
    c = idx.shape[0]

    def body(i, out):
        row = jax.lax.dynamic_slice_in_dim(full, jnp.minimum(idx[i], m - 1),
                                           1, 0)
        return jax.lax.dynamic_update_slice_in_dim(out, row, i, 0)

    return jax.lax.fori_loop(0, c, body, jnp.zeros((c, d), full.dtype))


@functools.partial(jax.jit, donate_argnums=(4,))
def mix_scatter_rows(w, theta, idx, mask, full):
    """``ref.masked_mix_scatter`` writing only the cohort rows in place.

    Args:
      w: (c, c) f32 mixing matrix (pad columns zero; pad rows arbitrary).
      theta: (c, d) cohort-stacked flat updates.
      idx: (c,) int32 target rows in ``full``; pad slots hold >= m.
      mask: (c,) bool/int, nonzero on real slots.
      full: (m, d) stacked client state, donated: the scatter updates it
        in place and untouched rows are never read or written.
    Returns:
      (m, d) updated state, in ``full.dtype``.
    """
    c = w.shape[0]
    _check(w.ndim == 2 and w.shape == (c, c),
           f"w must be square (c, c), got {w.shape}")
    _check(full.ndim == 2, f"full must be (m, d), got {full.shape}")
    m, d = full.shape
    _check(theta.shape == (c, d),
           f"theta must be {(c, d)} to match w {w.shape} and full "
           f"{full.shape}, got {theta.shape}")
    _check(idx.shape == (c,) and mask.shape == (c,),
           f"idx/mask must be ({c},), got {idx.shape}/{mask.shape}")
    mixed = ref.mix_aggregate(w, theta).astype(full.dtype)
    target = jnp.where(mask.astype(bool), idx, m)
    return full.at[target].set(mixed, mode="drop")
