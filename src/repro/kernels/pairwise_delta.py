"""Pallas TPU kernel for pairwise gradient distances (Eq. 9 input).

Computes the Gram matrix ``G G^T`` of the (m, d) stacked client gradients in
ONE streaming pass over d (the model dimension, potentially billions),
accumulating the (m, m) product in a VMEM-resident f32 tile. The naive
formulation (m^2 row-pair passes) reads G m times; this reads it once.
Distances ``||g_i - g_j||^2 = G_ii + G_jj - 2 G_ij`` are recovered from the
Gram matrix by the ops wrapper (O(m^2), negligible).

Grid iterates sequentially over d-blocks on TPU, so the output block (same
index every step) persists in VMEM and is accumulated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.masked_mix_scatter import _VMEM_BUDGET_FLOATS


DEFAULT_BLOCK_D = 4096


def _gram_kernel(g_ref, out_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.dot(g, g.T, preferred_element_type=jnp.float32)


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram_pallas(g, *, block_d: int = DEFAULT_BLOCK_D, interpret: bool = False):
    """Streaming Gram matrix of (m, d) -> (m, m) f32."""
    m, d = g.shape
    m_pad = _round_up(m, 8)
    # the pipeline double-buffers the (m_pad, block) input tile and the
    # (m_pad, m_pad) accumulator; cap the block so both fit the budget
    cap = (_VMEM_BUDGET_FLOATS - 2 * m_pad * m_pad) // (2 * m_pad)
    block_d = min(block_d, _round_up(d, 128), cap) // 128 * 128
    block_d = max(block_d, 128)
    d_pad = _round_up(d, block_d)
    g_p = jnp.zeros((m_pad, d_pad), g.dtype).at[:m, :d].set(g)

    grid = (d_pad // block_d,)
    out = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((m_pad, block_d), lambda j: (0, j))],
        out_specs=pl.BlockSpec((m_pad, m_pad), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(g_p)
    return out[:m, :m]
