"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
swept by tests/test_kernels.py)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, scale: Optional[float] = None,
                        causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0, return_lse: bool = False):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D] (GQA when Hq > Hkv).
    Positions are absolute: q row i has position q_offset + i. With
    ``return_lse`` also returns the [B, Hq, Sq] float32 row logsumexp
    (the residual the Pallas backward kernels recompute p from)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32)) * scale
    qp = jnp.arange(sq) + q_offset
    kp = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window is not None:
        mask &= kp[None, :] > qp[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    o = o.reshape(b, hq, sq, d).astype(q.dtype)
    if return_lse:
        lse = jax.scipy.special.logsumexp(s, axis=-1)   # [b, hkv, g, sq]
        return o, lse.reshape(b, hq, sq)
    return o


def _logical_kv(k_pages, v_pages, table, layer, d, k_scales, v_scales):
    """A request's logical K/V view through its block table: page-major
    stacks [L, NB, bs, Hkv * D] at ``layer`` gathered by table [..., T]
    -> float32 k, v [..., Hkv, T * bs, D], dequantized when the scales
    ([L, NB, 1, bs * Hkv], token ``t`` of head ``h`` at lane ``t * Hkv +
    h``) are given."""
    _, _, bs, w = k_pages.shape
    hkv = w // d
    lead = table.shape[:-1]
    n = table.shape[-1] * bs

    def view(pages, scales):
        x = pages[layer][table].astype(jnp.float32)   # [..., T, bs, w]
        x = x.reshape(lead + (n, hkv, d))
        if scales is not None:
            sc = scales[layer][table].reshape(lead + (n, hkv, 1))
            x = x * sc
        return jnp.moveaxis(x, -2, -3)               # [..., Hkv, n, D]
    return view(k_pages, k_scales), view(v_pages, v_scales)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, ctx_lens,
                               *, layer, scale: Optional[float] = None,
                               k_scales=None, v_scales=None):
    """Oracle for the paged single-token decode kernel.

    q: [B, Hq, D]; k_pages/v_pages: page-major layer stacks [L, NB, bs,
    Hkv * D], read at ``layer``; block_tables: [B, T] int32; ctx_lens:
    [B] int32. Gathers each request's logical KV view through its block
    table, dequantizes when scales ([L, NB, 1, bs * Hkv]) are given,
    masks positions >= ctx_len, and runs dense softmax attention.
    Requests with ``ctx_lens == 0`` return zeros (matching the kernel,
    which attends to nothing for them)."""
    b, hq, d = q.shape
    k, v = _logical_kv(k_pages, v_pages, block_tables, layer, d, k_scales,
                       v_scales)                      # [B, Hkv, T*bs, D]
    hkv, n = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k) * scale
    mask = jnp.arange(n)[None, :] < ctx_lens[:, None]   # [B, T*bs]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v)
    o = jnp.where(ctx_lens[:, None, None, None] > 0, o, 0.0)
    return o.reshape(b, hq, d).astype(q.dtype)


def paged_prefill_attention_ref(q, k_pages, v_pages, block_table, q_offset,
                                ctx_len, *, layer,
                                scale: Optional[float] = None,
                                k_scales=None, v_scales=None):
    """Oracle for the chunked paged-prefill kernel.

    q: [Hq, C, D] (row ``c`` at absolute position ``q_offset + c``);
    k_pages/v_pages: page-major layer stacks [L, NB, bs, Hkv * D] already
    holding the chunk's own K/V, read at ``layer``; block_table: [T]
    int32. Gathers the request's logical KV view through its table,
    dequantizes when scales are given, masks causally from absolute
    positions (``kp <= q_offset + c`` and ``kp < ctx_len``), and runs
    dense softmax attention. Rows past ``chunk_len = ctx_len - q_offset``
    are padding and return garbage values the caller discards — the
    comparison against the kernel slices them off.
    """
    hq, c, d = q.shape
    k, v = _logical_kv(k_pages, v_pages, block_table, layer, d, k_scales,
                       v_scales)                      # [Hkv, T*bs, D]
    hkv, n = k.shape[0], k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    qg = q.reshape(hkv, g, c, d).astype(jnp.float32)
    s = jnp.einsum("hgcd,hkd->hgck", qg, k) * scale
    qp = q_offset + jnp.arange(c)
    kp = jnp.arange(n)
    mask = (kp[None, :] <= qp[:, None]) & (kp[None, :] < ctx_len)
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgck,hkd->hgcd", p, v)
    return o.reshape(hq, c, d).astype(q.dtype)


def mlstm_chunked_ref(q, k, v, ig, lf, *, chunk: int = 64, C0=None, n0=None,
                      m0=None):
    """Stabilized mLSTM over the sequence, step-by-step (the exact
    recurrence the chunked kernel reproduces).

    q/k/v: [B, NH, S, DH] (k pre-scaled); ig/lf: [B, NH, S].
    Returns (h [B, NH, S, DH], (C, n, m) final states).
    """
    b, nh, s, dh = q.shape
    C = jnp.zeros((b, nh, dh, dh), jnp.float32) if C0 is None else C0
    n = jnp.zeros((b, nh, dh), jnp.float32) if n0 is None else n0
    m = jnp.full((b, nh), -1e30, jnp.float32) if m0 is None else m0

    def step(carry, t):
        C, n, m = carry
        q_t, k_t, v_t, i_t, lf_t = t
        m_new = jnp.maximum(lf_t + m, i_t)
        fs = jnp.exp(lf_t + m - m_new)[..., None]
        is_ = jnp.exp(i_t - m_new)[..., None]
        C = fs[..., None] * C + is_[..., None] * (v_t[..., :, None]
                                                  * k_t[..., None, :])
        n = fs * n + is_ * k_t
        num = jnp.einsum("bhij,bhj->bhi", C, q_t)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhj,bhj->bh", n, q_t)),
                          jnp.exp(-m_new))[..., None]
        return (C, n, m_new), num / den

    ts = (q.transpose(2, 0, 1, 3).astype(jnp.float32),
          k.transpose(2, 0, 1, 3).astype(jnp.float32),
          v.transpose(2, 0, 1, 3).astype(jnp.float32),
          ig.transpose(2, 0, 1).astype(jnp.float32),
          lf.transpose(2, 0, 1).astype(jnp.float32))
    (C, n, m), hs = jax.lax.scan(step, (C, n, m), ts)
    return hs.transpose(1, 2, 0, 3).astype(q.dtype), (C, n, m)


def quantize_int8_ref(x, bits):
    """Rowwise-absmax int8 stochastic quantization (oracle for
    kernels/quantize.py). x: [M, 128] float; bits: [M, 128] uint32.
    Returns (q int8 [M, 128], scale float32 [M, 1]); all-zero rows emit
    scale 0 / q 0."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    safe = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24)   # top 24 bits
    q = jnp.clip(jnp.floor(xf / safe + u), -127.0, 127.0).astype(jnp.int8)
    scale = jnp.where(absmax > 0.0, safe, 0.0)
    return q, scale


def dequantize_int8_ref(q, scale, *, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8_ref`: ``q * scale``."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def lora_matmul_ref(x, w, a, b, *, scale: float = 1.0):
    """y = x @ w + scale * (x @ a) @ b.

    x: [M, K]; w: [K, N]; a: [K, r]; b: [r, N]."""
    base = x.astype(jnp.float32) @ w.astype(jnp.float32)
    low = (x.astype(jnp.float32) @ a.astype(jnp.float32)) \
        @ b.astype(jnp.float32)
    return (base + scale * low).astype(x.dtype)
