"""Legal Mosaic tiles for the row/column dims of the 2-D kernels.

Mosaic accepts a block dim that is a multiple of the hardware tile
(8 sublanes or 128 lanes; 32 rows for int8) or the whole array dim. A
largest-divisor tile can be neither (300 rows -> 150), so kernels take
their tiles from :func:`tile` and zero-pad the operand to the returned
size where no legal tile divides it.
"""
from __future__ import annotations

import jax.numpy as jnp


def tile(block: int, dim: int, align: int):
    """(tile, padded dim) for ``dim`` under a requested ``block``: the
    whole dim when it fits in max(block, align), else the largest
    multiple of ``align`` up to ``block`` that divides ``dim``, else the
    largest multiple of ``align`` up to ``block`` with ``dim`` padded up
    to a multiple of it."""
    if dim <= max(block, align):
        return dim, dim
    top = max(align, block - block % align)
    for t in range(top, 0, -align):
        if dim % t == 0:
            return t, dim
    return top, -(-dim // top) * top


def pad2(x, rows: int, cols: int):
    """Zero-pad a 2-D array up to [rows, cols]."""
    if x.shape == (rows, cols):
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))
