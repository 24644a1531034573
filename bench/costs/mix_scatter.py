"""Operations and HBM bytes of one slab mix-scatter call.

The slab formulation streams the whole (m, d) float32 client state
through the kernel and back (copy-through of the rows it does not
write), reads the (c, d) cohort uploads, the (c, c) mixing rows and the
(c,) int32 slot indices and mask, and multiplies the rows with the
uploads on the MXU. ``d`` is the state's (128-aligned) width. Counted at
the sizes of the call, without the kernel's own tile padding.
"""
from __future__ import annotations


def hbm_bytes(m: int, c: int, d: int) -> int:
    return (2 * m + c) * d * 4 + c * c * 4 + c * 8


def flops(m: int, c: int, d: int) -> int:
    return 2 * c * c * d
