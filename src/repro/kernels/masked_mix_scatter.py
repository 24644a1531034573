"""Fused Pallas TPU kernel: masked cohort mix + scatter into the full state.

The padded-cohort round engine produces a cohort-stacked update matrix
theta (c, d) plus a (c, c) row-renormalized mixing matrix whose pad
columns are zero. PR 1 applied the mix with one ``mix_aggregate`` launch
per pytree leaf and then scattered the result back into the (m, d)
stacked client state as a separate XLA scatter — two full passes over the
cohort's bytes plus a kernel launch per leaf. This kernel fuses both:

  out = full;  out[idx[i]] = (W @ theta)[i]   for every slot with mask[i]

in ONE pass over the data. The grid walks the d axis; each step keeps W
resident, streams a (c, BLOCK_D) tile of theta through VMEM, multiplies
on the MXU and row-scatters the masked results into the (m, BLOCK_D)
output slab. ``full`` is aliased to the output (``input_output_aliases``)
so — together with ``donate_argnums`` at the jit level — the (m, d)
stacked state is updated without allocating a second copy.

Traffic honesty — the two regimes:

  * **VMEM slab (this kernel).** The slab formulation *streams* the full
    state through VMEM (copy-through of untouched rows), so HBM traffic
    is ~(2·m + c)·d floats per call; the fusion saves the extra
    mix-output allocation, the per-leaf launch overhead, and the
    separate XLA scatter pass — not the state read. ``block_d`` is
    clamped so every buffer the pipeline allocates (:func:`_slab_cap`)
    fits the compiler's scoped VMEM limit, which bounds single-call m:
    once ``4·m_pad + 3·c_pad`` rows can't sustain even a 128-wide block
    (m_pad ≈ 6k rows at c=64), the slab is infeasible.
  * **HBM-resident** (:mod:`repro.kernels.cohort_rows`). ``full`` stays
    in HBM and XLA moves only the c cohort rows — traffic O(c·d) at any
    m, no m-dependent VMEM bound, and no padding at all.

:func:`slab_fits` is the boundary between the regimes;
:func:`repro.kernels.ops.masked_mix_scatter` auto-dispatches on it
(``impl`` suffix ``_slab`` / ``_hbm`` forces either side, also via the
``REPRO_KERNEL_IMPL`` env var).

Alignment: tile shapes need d divisible by the block (multiple of 128)
and m_pad divisible by 8. When d is 128-aligned a divisor block is
chosen automatically and the state is used zero-copy; otherwise the
state is zero-padded into an aligned buffer (a full copy — callers with
hot unaligned states should pad d to 128 up front).

Slot contract (owned by :mod:`repro.federated.participation`): pad slots
carry an out-of-range sentinel index (>= m) and ``mask[i] == 0``; the
kernel predicates the row store on both, so pad slots never write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_D = 2048


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


# Everything the pipeline allocates must fit the compiler's default
# scoped VMEM limit (16 MiB on v5e); this budget keeps 4 MiB of it free.
_VMEM_BUDGET_FLOATS = 3 << 20


def _slab_cap(m_pad: int, c_pad: int) -> int:
    """Widest block (in columns) whose buffers fit the VMEM budget. The
    pipeline double-buffers every blocked operand: the full-state input
    and the output (4 (m_pad, block) slabs), theta (2 (c_pad, block)
    tiles) and W (2 (c_pad, c_pad)); the mix scratch adds one more
    (c_pad, block) tile."""
    return ((_VMEM_BUDGET_FLOATS - 2 * c_pad * c_pad)
            // (4 * m_pad + 3 * c_pad))


def _pick_block_d(block_d: int, d: int, m_pad: int, c_pad: int) -> int:
    cap = max(_slab_cap(m_pad, c_pad), 128)
    block_d = max(min(block_d, cap) // 128 * 128, 128)
    if d % 128 == 0:
        # pick a divisor of d so the d axis needs no padding at all
        while d % block_d:
            block_d -= 128
    return block_d


def slab_fits(m: int, c: int) -> bool:
    """True when the VMEM-slab formulation is feasible for (m, c): its
    buffers (:func:`_slab_cap`) must sustain at least a 128-wide block
    inside the VMEM budget. Past this bound (m_pad ≈ 6k rows at c=64)
    :mod:`repro.kernels.ops` auto-selects the HBM-resident row path."""
    return _slab_cap(_round_up(int(m), 8), _round_up(int(c), 8)) >= 128


def padding_copy_needed(m: int, c: int, d: int,
                        block_d: int = DEFAULT_BLOCK_D) -> bool:
    """True when :func:`masked_mix_scatter_pallas` must zero-pad ``full``
    into an aligned (m_pad, d_pad) buffer — a full O(m·d) copy that
    forfeits the aliased zero-copy path. False exactly when m is a
    multiple of 8 and d is a multiple of 128 (the alignment
    :func:`repro.kernels.ops.aligned_dim` provides at state creation)."""
    c_pad = _round_up(int(c), 8)
    m_pad = _round_up(int(m), 8)
    block = _pick_block_d(min(int(block_d), _round_up(int(d), 128)), int(d),
                          m_pad, c_pad)
    d_pad = _round_up(int(d), block)
    return (m_pad, d_pad) != (int(m), int(d))


def _kernel(idx_ref, mask_ref, w_ref, theta_ref, full_ref, out_ref, mix_ref,
            *, c, m):
    # Copy-through of the untouched rows (a no-op self-copy when the
    # output buffer aliases ``full``), then overwrite the cohort rows.
    out_ref[...] = full_ref[...]
    # the mix goes through a VMEM scratch ref: Mosaic lowers a dynamic
    # one-row slice of a ref, not of a value
    mix_ref[...] = jnp.dot(
        w_ref[...].astype(jnp.float32), theta_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(mix_ref.dtype)

    def body(i, carry):
        r = idx_ref[i]

        @pl.when((mask_ref[i] != 0) & (r < m))
        def _():
            out_ref[pl.ds(r, 1), :] = mix_ref[pl.ds(i, 1), :]

        return carry

    jax.lax.fori_loop(0, c, body, 0)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"),
                   donate_argnums=(4,))
def masked_mix_scatter_pallas(w, theta, idx, mask, full, *,
                              block_d: int = DEFAULT_BLOCK_D,
                              interpret: bool = False):
    """Pallas implementation of ``ref.masked_mix_scatter``.

    Args:
      w: (c, c) f32 mixing matrix (pad columns zero; pad rows arbitrary).
      theta: (c, d) cohort-stacked flat updates.
      idx: (c,) int32 target rows in ``full``; pad slots hold >= m.
      mask: (c,) bool/int, nonzero on real slots.
      full: (m, d) stacked client state, donated and aliased into the
        output so unwritten rows never move through HBM.
    Returns:
      (m, d) updated state, in ``full.dtype``.
    """
    c = w.shape[0]
    # ValueError (not assert): shape contracts must survive python -O
    if w.ndim != 2 or w.shape != (c, c):
        raise ValueError(f"w must be square (c, c), got {w.shape}")
    if full.ndim != 2:
        raise ValueError(f"full must be (m, d), got {full.shape}")
    m, d = full.shape
    if theta.shape != (c, d):
        raise ValueError(
            f"theta must be {(c, d)} to match w {w.shape} and full "
            f"{full.shape}, got {theta.shape}")
    if idx.shape != (c,) or mask.shape != (c,):
        raise ValueError(
            f"idx/mask must be ({c},), got {idx.shape}/{mask.shape}")
    c_pad = _round_up(c, 8)
    m_pad = _round_up(m, 8)
    block_d = _pick_block_d(min(block_d, _round_up(d, 128)), d, m_pad, c_pad)
    d_pad = _round_up(d, block_d)
    # Zero-pad W/theta (small); ``full`` is only padded when the state is
    # not tile-aligned — aligned states take the zero-copy aliased path.
    w_p = jnp.zeros((c_pad, c_pad), w.dtype).at[:c, :c].set(w)
    theta_p = jnp.zeros((c_pad, d_pad), theta.dtype).at[:c, :d].set(theta)
    padded = (m_pad, d_pad) != (m, d)
    full_p = (jnp.zeros((m_pad, d_pad), full.dtype).at[:m, :d].set(full)
              if padded else full)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d_pad // block_d,),
        in_specs=[
            pl.BlockSpec((c_pad, c_pad), lambda j, *_: (0, 0)),
            pl.BlockSpec((c_pad, block_d), lambda j, *_: (0, j)),
            pl.BlockSpec((m_pad, block_d), lambda j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((m_pad, block_d), lambda j, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((c_pad, block_d), full.dtype)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, c=c, m=m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, d_pad), full.dtype),
        input_output_aliases={4: 0},  # full_p -> out, in-place row writes
        interpret=interpret,
    )(idx.astype(jnp.int32), mask.astype(jnp.int32), w_p, theta_p, full_p)
    return out[:m, :d] if padded else out
