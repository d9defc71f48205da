"""Fused base + low-rank matmul: y = x @ W + scale * (x @ A) @ B.

CELLAdapt's edge fine-tuning (paper §5.2) runs LoRA-adapted layers at
serving time; unfused, the low-rank path re-reads x from HBM and
materializes x@A. The kernel accumulates BOTH the base tile product and
the rank-r projection in VMEM across the K grid dimension and applies the
B projection once on the last K step — one pass over x and W.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import pad2, tile


def _kernel(x_ref, w_ref, a_ref, b_ref, y_ref, acc_ref, xa_ref, *,
            scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(x, w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    xa_ref[...] += jax.lax.dot(x, a_ref[...].astype(jnp.float32),
                               preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        low = jax.lax.dot(xa_ref[...], b_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        y_ref[...] = (acc_ref[...] + scale * low).astype(y_ref.dtype)


def lora_matmul(x, w, a, b, *, scale: float = 1.0, block_m: int = 256,
                block_n: int = 256, block_k: int = 512,
                interpret: bool = False):
    """x: [M, K]; w: [K, N]; a: [K, r]; b: [r, N] -> [M, N]. Any dims:
    tiles are clamped to legal Mosaic blocks and the operands
    zero-padded where no legal tile divides a dim (zeros add nothing to
    the products; the padded output rows/cols are sliced off)."""
    m, kdim = x.shape
    n = w.shape[1]
    r = a.shape[1]
    bm, mp = tile(block_m, m, 8)
    bn, np_ = tile(block_n, n, 128)
    bk, kp = tile(block_k, kdim, 128)
    x, w = pad2(x, mp, kp), pad2(w, kp, np_)
    a, b = pad2(a, kp, r), pad2(b, r, np_)
    grid = (mp // bm, np_ // bn, kp // bk)

    kernel = functools.partial(_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((bk, r), lambda mi, ni, ki: (ki, 0)),
            pl.BlockSpec((r, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, a, b)[:m, :n]
