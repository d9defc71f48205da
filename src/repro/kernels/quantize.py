"""Int8 stochastic quantize/dequantize Pallas kernel pair.

The uplink hot path of the :mod:`repro.comm` fabric: every FL round each
vehicle compresses its full model delta before transmission (paper §3.1
— the cloud-edge-vehicle hierarchy exists to cut communication time, and
update compression is the per-link half of that). Unfused, XLA issues
separate absmax / divide / round passes over the delta; the kernel does
one pass per tile — rowwise absmax scale, stochastic round, int8 store —
keeping the tile in VMEM throughout.

Layout contract (enforced by :func:`repro.comm.codecs.Int8Codec`): the
flat delta is reshaped to rows of 128 lanes, ``x: [M, 128]`` float, with
one float32 scale per row. Randomness comes in as explicit uint32 bits
(``jax.random.bits`` outside the kernel) so the pair is deterministic
given its inputs and runs identically under interpret mode — no
``custom_vjp`` anywhere: encode/decode is a plain function pair outside
the differentiated path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import pad2, tile


LANES = 128          #: fixed lane width of the quantization row layout
QMAX = 127.0         #: symmetric int8 range
ROW_ALIGN = 32       #: int8 sublane tile: row blocks are multiples of this


def uniform24(bits):
    """u ~ U[0, 1) from uint32 random bits: the top 24 bits, scaled by
    2**-24. Goes through int32 because Mosaic has no uint32 -> float32
    cast; every 24-bit integer is exact in float32, and 2**31 maps to
    exactly 0.5."""
    top = jax.lax.shift_right_logical(
        jax.lax.bitcast_convert_type(bits, jnp.int32), 8)
    return top.astype(jnp.float32) * (2.0 ** -24)


def _quant_kernel(x_ref, bits_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                     # [bm, 128]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)    # [bm, 1]
    scale = jnp.where(absmax > 0.0, absmax / QMAX, 1.0)
    scale_ref[...] = jnp.where(absmax > 0.0, scale, 0.0)
    # unbiased stochastic rounding: E[floor(s + u)] = s for u ~ U[0, 1)
    u = uniform24(bits_ref[...])
    s = x / scale
    q = jnp.clip(jnp.floor(s + u), -QMAX, QMAX)
    q_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(q_ref, scale_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * scale_ref[...]).astype(x_ref.dtype)


def quantize_int8(x, bits, *, block_rows: int = 256,
                  interpret: bool = False):
    """x: [M, 128] float; bits: [M, 128] uint32 random bits.

    Returns ``(q int8 [M, 128], scale float32 [M, 1])`` with rowwise
    symmetric absmax scales (all-zero rows emit scale 0 and q 0)."""
    m, n = x.shape
    assert n == LANES, f"quantize rows must be {LANES} lanes wide, got {n}"
    assert bits.shape == x.shape
    bm, mp = tile(block_rows, m, ROW_ALIGN)   # zero rows: q 0, scale 0
    q, scale = pl.pallas_call(
        _quant_kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda mi: (mi, 0)),
            pl.BlockSpec((bm, n), lambda mi: (mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda mi: (mi, 0)),
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, n), jnp.int8),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad2(x, mp, n), pad2(bits, mp, n))
    return q[:m], scale[:m]


def dequantize_int8(q, scale, *, dtype=jnp.float32, block_rows: int = 256,
                    interpret: bool = False):
    """Inverse of :func:`quantize_int8`: ``q * scale`` -> [M, 128]."""
    m, n = q.shape
    assert n == LANES
    assert scale.shape == (m, 1)
    bm, mp = tile(block_rows, m, ROW_ALIGN)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda mi: (mi, 0)),
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda mi: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad2(q, mp, n), pad2(scale, mp, 1))
    return x[:m]
