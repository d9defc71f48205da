"""jit'd public wrappers over the Pallas kernels.

On a TPU the kernels compile to Mosaic; on the CPU (where the tests run)
they run in interpret mode — the kernel body executes in Python for
correctness validation. ``interpret=None`` picks by backend and refuses
any other backend.

Autodiff: ``flash_attention_ad`` and ``lora_matmul_ad`` carry
``custom_vjp`` rules whose backward passes are themselves kernels —
flash attention saves ``(q, k, v, o, lse)`` residuals and runs the
preprocess/dKV/dQ Pallas kernels (O(S·D) memory; no O(Sq·Skv) score
matrix is ever materialized), and the LoRA matmul's closed-form dx reuses
the fused forward kernel on transposed operands.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import lora_matmul as _lm
from repro.kernels import mlstm as _ml
from repro.kernels import moe as _moe
from repro.kernels import quantize as _qz


def _auto_interpret(interpret: Optional[bool]) -> bool:
    """Compile with Mosaic on a TPU; interpret on the CPU, where the
    tests run. Any other backend has no Pallas TPU path, and silently
    interpreting there would hide that the device is not being used."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels compile only for 'tpu' and interpret only on "
        f"'cpu'; the default backend is {backend!r}")


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "q_offset", "block_q",
                                             "block_k", "return_lse",
                                             "interpret"))
def flash_attention(q, k, v, *, scale=None, causal=True, window=None,
                    q_offset=0, block_q=128, block_k=128, return_lse=False,
                    interpret=None):
    return _fa.flash_attention(q, k, v, scale=scale, causal=causal,
                               window=window, q_offset=q_offset,
                               block_q=block_q, block_k=block_k,
                               return_lse=return_lse,
                               interpret=_auto_interpret(interpret))


# Differentiable flash attention: the VJP runs the real backward kernels
# from the saved (q, k, v, o, lse) residuals instead of re-linearizing the
# O(S^2) reference implementation.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fa_ad(q, k, v, scale, causal, window, q_offset, block_q, block_k,
           interpret):
    return _fa.flash_attention(q, k, v, scale=scale, causal=causal,
                               window=window, q_offset=q_offset,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def _fa_ad_fwd(q, k, v, scale, causal, window, q_offset, block_q, block_k,
               interpret):
    o, lse = _fa.flash_attention(q, k, v, scale=scale, causal=causal,
                                 window=window, q_offset=q_offset,
                                 block_q=block_q, block_k=block_k,
                                 return_lse=True, interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_ad_bwd(scale, causal, window, q_offset, block_q, block_k,
               interpret, res, g):
    q, k, v, o, lse = res
    return _fa.flash_attention_bwd(
        q, k, v, o, lse, g, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)


_fa_ad.defvjp(_fa_ad_fwd, _fa_ad_bwd)


def flash_attention_ad(q, k, v, scale=None, causal=True, window=None,
                       q_offset=0, *, block_q=128, block_k=128,
                       interpret=None):
    """Differentiable flash attention (kernel forward AND backward).
    ``block_q``/``block_k`` tune the VMEM tiles of both passes."""
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    return _fa_ad(q, k, v, scale, causal, window, q_offset,
                  int(block_q), int(block_k), _auto_interpret(interpret))


# Serving hot path (repro.serve): single-token decode against the paged
# KV pool. No autodiff — decode never backpropagates.
@functools.partial(jax.jit, static_argnames=("scale", "latent_v",
                                             "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                           layer, scale=None, k_scales=None, v_scales=None,
                           latent_v=0, interpret=None):
    """q: [B, Hq, D] decode queries; k_pages/v_pages: page-major layer
    stacks [L, NB, bs, Hkv * D], read at ``layer`` (int32 scalar);
    block_tables: [B, T] logical->physical maps; ctx_lens: [B] visible
    KV lengths. Pass ``k_scales``/``v_scales`` ([L, NB, 1, bs * Hkv])
    for int8 pools (dequantized in-kernel). Returns [B, Hq, D]. Latent
    attention passes ``v_pages=None`` and ``latent_v``: values are the
    first ``latent_v`` lanes of the one pool's rows (returns [B, Hq,
    latent_v])."""
    return _fa.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                      ctx_lens, layer=layer, scale=scale,
                                      k_scales=k_scales, v_scales=v_scales,
                                      latent_v=latent_v,
                                      interpret=_auto_interpret(interpret))


# Serving hot path (repro.serve): one prompt chunk against the paged KV
# pool. q_offset/ctx_len stay traced so every chunk of every prompt
# length shares one compiled call. No autodiff — prefill never
# backpropagates.
@functools.partial(jax.jit, static_argnames=("scale", "latent_v",
                                             "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, block_table, q_offset,
                            ctx_len, *, layer, scale=None, k_scales=None,
                            v_scales=None, latent_v=0, interpret=None):
    """q: [Hq, C, D] query chunk (row c at position q_offset + c);
    k_pages/v_pages: page-major layer stacks [L, NB, bs, Hkv * D]
    already holding the chunk's own K/V rows, read at ``layer``;
    block_table: [T] logical->physical map; q_offset/ctx_len: int32
    scalars (ctx_len = q_offset + chunk_len). Pass
    ``k_scales``/``v_scales`` for int8 pools (dequantized in-kernel).
    Returns [Hq, C, D]; rows past chunk_len are garbage. Latent
    attention passes ``v_pages=None`` and ``latent_v`` as in
    :func:`paged_decode_attention`."""
    return _fa.paged_prefill_attention(q, k_pages, v_pages, block_table,
                                       q_offset, ctx_len, layer=layer,
                                       scale=scale, k_scales=k_scales,
                                       v_scales=v_scales, latent_v=latent_v,
                                       interpret=_auto_interpret(interpret))


# Serving hot path (repro.serve): speculative-decode verification. A
# draft window is exactly a chunk of C = k+1 decode positions attending
# through the lane's block table, so verification reuses the chunked
# prefill kernel per lane — the lane loop is static (slots is a compile
# constant) and unrolls into independent kernel calls inside one jit.
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_verify_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                           chunk_lens, *, layer, scale=None, k_scales=None,
                           v_scales=None, interpret=None):
    """q: [B, Hq, C, D] per-lane draft-window queries (row c of lane b at
    position ctx_lens[b] + c); k_pages/v_pages: page-major layer stacks
    [L, NB, bs, Hkv * D] already holding the window's own K/V rows, read
    at ``layer``; block_tables: [B, T]; ctx_lens/chunk_lens: [B] int32
    (lane b's window covers positions [ctx_lens[b], ctx_lens[b] +
    chunk_lens[b])). Returns [B, Hq, C, D]; rows at or past a lane's
    chunk_len are garbage."""
    outs = [
        _fa.paged_prefill_attention(
            q[b], k_pages, v_pages, block_tables[b], ctx_lens[b],
            ctx_lens[b] + chunk_lens[b], layer=layer, scale=scale,
            k_scales=k_scales, v_scales=v_scales,
            interpret=_auto_interpret(interpret))
        for b in range(q.shape[0])
    ]
    return jnp.stack(outs)


# Serving hot path (repro.serve): the held experts of an MoE layer as
# one grouped matmul over expert-sorted, tile-padded rows.
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def moe_expert_ffn(x, tile_expert, n_live, layer, wi, wg, wo, *, tile,
                   interpret=None):
    """x: [M, d] rows sorted by expert, each expert's padded to whole
    ``tile``s; tile_expert: [M / tile] int32; n_live: [1] int32 live
    tiles; layer: [1] int32, the layer of the stacks wi/wg: [L, E, d, f],
    wo: [L, E, f, d]. Returns [M, d] (zero rows past the live tiles)."""
    return _moe.moe_expert_ffn(x, tile_expert, n_live, layer, wi, wg, wo,
                               tile=tile,
                               interpret=_auto_interpret(interpret))


# Codec hot path (repro.comm): no custom_vjp — encode/decode runs outside
# the differentiated path, so the pair stays a plain kernel call.
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_int8(x, bits, *, block_rows=256, interpret=None):
    """Rowwise int8 stochastic quantization of [M, 128] rows; ``bits``
    are explicit uint32 randomness (jax.random.bits) so the call is
    deterministic given its inputs."""
    return _qz.quantize_int8(x, bits, block_rows=block_rows,
                             interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("dtype", "block_rows",
                                             "interpret"))
def dequantize_int8(q, scale, *, dtype=jnp.float32, block_rows=256,
                    interpret=None):
    return _qz.dequantize_int8(q, scale, dtype=dtype,
                               block_rows=block_rows,
                               interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunked(q, k, v, ig, lf, *, chunk=64, interpret=None):
    return _ml.mlstm_chunked(q, k, v, ig, lf, chunk=chunk,
                             interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "block_k", "interpret"))
def lora_matmul(x, w, a, b, *, scale=1.0, block_m=256, block_n=256,
                block_k=512, interpret=None):
    return _lm.lora_matmul(x, w, a, b, scale=scale, block_m=block_m,
                           block_n=block_n, block_k=block_k,
                           interpret=_auto_interpret(interpret))


# Differentiable fused LoRA matmul: the raw pallas_call has no autodiff
# rule, so the distillation path could not differentiate through the
# fused kernel at all. Closed form for y = x@w + scale*(x@a)@b:
#   dx = g @ w^T + scale*(g @ b^T) @ a^T   (the same fused kernel, on
#                                           transposed operands)
#   dw = x^T @ g
#   da = scale * x^T @ (g @ b^T)
#   db = scale * (x @ a)^T @ g
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _lora_ad(x, w, a, b, scale, block_m, block_n, block_k, interpret):
    return _lm.lora_matmul(x, w, a, b, scale=scale, block_m=block_m,
                           block_n=block_n, block_k=block_k,
                           interpret=interpret)


def _lora_ad_fwd(x, w, a, b, scale, block_m, block_n, block_k, interpret):
    out = _lora_ad(x, w, a, b, scale, block_m, block_n, block_k, interpret)
    return out, (x, w, a, b)


def _lora_ad_bwd(scale, block_m, block_n, block_k, interpret, res, g):
    x, w, a, b = res
    dx = _lm.lora_matmul(
        g, w.T, b.T, a.T, scale=scale, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interpret).astype(x.dtype)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dw = (xf.T @ gf).astype(w.dtype)
    gb = gf @ b.astype(jnp.float32).T
    da = (scale * (xf.T @ gb)).astype(a.dtype)
    xa = xf @ a.astype(jnp.float32)
    db = (scale * (xa.T @ gf)).astype(b.dtype)
    return dx, dw, da, db


_lora_ad.defvjp(_lora_ad_fwd, _lora_ad_bwd)


def lora_matmul_ad(x, w, a, b, *, scale=1.0, block_m=256, block_n=256,
                   block_k=512, interpret=None):
    """Differentiable fused LoRA matmul (closed-form VJP; dx reuses the
    fused kernel). Tiles are clamped to legal Mosaic blocks per dim."""
    return _lora_ad(x, w, a, b, float(scale), int(block_m), int(block_n),
                    int(block_k), _auto_interpret(interpret))
