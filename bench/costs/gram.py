"""Operations and HBM bytes of the Eq. 9 Gram kernel.

The kernel reads the (m, d) float32 gradients once and writes the
(m, m) float32 Gram matrix; it forms every product g_i . g_j, so
2 m^2 d operations. ``d`` is the model's true width: the gradients are
not lane-aligned. Counted at the sizes of the call, without the kernel's
own tile padding.
"""
from __future__ import annotations


def hbm_bytes(m: int, d: int) -> int:
    return m * d * 4 + m * m * 4


def flops(m: int, d: int) -> int:
    return 2 * m * m * d
