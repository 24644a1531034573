"""Pallas TPU kernel for pairwise gradient distances (Eq. 9 input).

Computes the Gram matrix ``G G^T`` of the (m, d) stacked client gradients,
tiled over (i, j) blocks of the (m, m) output. The grid is (i, j, k): i and
j pick an output tile, and k, the innermost axis, streams the d (model
dimension) blocks of the tile's row blocks i and j, accumulating their
product in the f32 output tile, which stays in VMEM across k. Each output
tile reads its two row blocks once, so G is read once per tile row (m_pad /
block_m times). When the whole (m_pad, m_pad) output fits one tile, the
grid has one output block and G is read once, through a single input.
Distances ``||g_i - g_j||^2 = G_ii + G_jj - 2 G_ij`` are recovered from the
Gram matrix by the ops wrapper (O(m^2), negligible).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.masked_mix_scatter import _VMEM_BUDGET_FLOATS


DEFAULT_BLOCK_D = 4096
DEFAULT_BLOCK_M = 512


def _gram_kernel(*refs):
    # refs: the row blocks i and j (one ref when they are the same rows),
    # then the (i, j) output tile
    *rows, out_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = rows[0][...].astype(jnp.float32)
    b = rows[-1][...].astype(jnp.float32)
    out_ref[...] += jnp.dot(a, b.T, preferred_element_type=jnp.float32)


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def tiles(m: int, d: int, block_m: int = DEFAULT_BLOCK_M,
          block_d: int = DEFAULT_BLOCK_D) -> tuple:
    """``(tile_m, tile_d, inputs)`` of the kernel at (m, d).

    ``m`` rounded up to 8 that fits ``block_m`` is one output tile read
    through one input; past it the output is cut into tiles of equal
    size, a multiple of 128 and at most ``block_m`` rounded down to one
    (at least 128), each reading two row blocks. ``tile_d`` is the widest
    block up to ``block_d`` whose buffers fit the VMEM budget: the
    pipeline double-buffers every input tile and the output tile."""
    m8 = _round_up(m, 8)
    if m8 <= block_m:
        tile_m, inputs = m8, 1
    else:
        most = max(block_m // 128 * 128, 128)
        n = -(-m8 // most)
        tile_m, inputs = _round_up(-(-m8 // n), 128), 2
    cap = (_VMEM_BUDGET_FLOATS - 2 * tile_m * tile_m) // (2 * inputs * tile_m)
    tile_d = max(min(block_d, _round_up(d, 128), cap) // 128 * 128, 128)
    return tile_m, tile_d, inputs


@functools.partial(jax.jit, static_argnames=("block_m", "block_d",
                                             "interpret", "stage"))
def gram_pallas(g, *, block_m: int = DEFAULT_BLOCK_M,
                block_d: int = DEFAULT_BLOCK_D, interpret: bool = False,
                stage: str | None = None):
    """Tiled Gram matrix of (m, d) -> (m, m) f32. ``stage`` names a scope
    for the program's ops (a device stage in a trace)."""
    with jax.named_scope(stage) if stage else contextlib.nullcontext():
        m, d = g.shape
        tile_m, tile_d, inputs = tiles(m, d, block_m, block_d)
        m_pad, d_pad = _round_up(m, tile_m), _round_up(d, tile_d)
        g_p = jnp.zeros((m_pad, d_pad), g.dtype).at[:m, :d].set(g)

        in_specs = [pl.BlockSpec((tile_m, tile_d), lambda i, j, k: (i, k)),
                    pl.BlockSpec((tile_m, tile_d), lambda i, j, k: (j, k))]
        out = pl.pallas_call(
            _gram_kernel,
            grid=(m_pad // tile_m, m_pad // tile_m, d_pad // tile_d),
            in_specs=in_specs[:inputs],
            out_specs=pl.BlockSpec((tile_m, tile_m), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m_pad, m_pad), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="gram_pallas",
        )(*[g_p] * inputs)
        return out[:m, :m]
