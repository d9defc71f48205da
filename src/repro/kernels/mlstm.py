"""Chunked mLSTM (xLSTM) as a Pallas TPU kernel.

TPU adaptation: the per-timestep recurrence (matrix memory C updated every
token) is hostile to the MXU — the chunkwise form turns intra-chunk work
into [c, c] / [c, DH] matmuls while only the inter-chunk (C, n, m) carry is
sequential, held in VMEM scratch across the innermost grid dimension.
Same stabilized math as models/recurrent.mlstm_chunk_body and
ref.mlstm_chunked_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, ig_ref, lf_ref, h_ref,
            c_fin_ref, n_fin_ref, m_fin_ref,
            C_ref, n_ref, m_ref, *, chunk: int, dh: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    q = q_ref[0, 0].astype(jnp.float32)          # [c, dh]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    ig = ig_ref[0, 0].astype(jnp.float32)        # [c]
    lf = lf_ref[0, 0].astype(jnp.float32)

    C, n, m = C_ref[...], n_ref[...][:, 0], m_ref[0, 0]

    b_ = jnp.cumsum(lf)                          # [c]
    a_ = ig - b_
    M = jax.lax.cummax(a_, axis=0)
    m_t = b_ + jnp.maximum(m, M)                 # [c]
    m_out = m_t[-1]

    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    D = b_[:, None] - b_[None, :] + ig[None, :] - m_t[:, None]
    D = jnp.where(kj <= pos, jnp.exp(D), 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    inter = jnp.exp(m + b_ - m_t)                # [c]
    num = jax.lax.dot(s * D, v, preferred_element_type=jnp.float32) \
        + inter[:, None] * jax.lax.dot(q, C.T,
                                       preferred_element_type=jnp.float32)
    n_t = jax.lax.dot(D, k, preferred_element_type=jnp.float32) \
        + inter[:, None] * n[None, :]
    den = jnp.maximum(jnp.abs((n_t * q).sum(axis=1)), jnp.exp(-m_t))
    h_ref[0, 0] = (num / den[:, None]).astype(h_ref.dtype)

    w_k = jnp.exp(b_[-1] - b_ + ig - m_out)      # [c]
    carry = jnp.exp(m + b_[-1] - m_out)
    C_ref[...] = carry * C + jax.lax.dot(
        (v * w_k[:, None]).T, k, preferred_element_type=jnp.float32)
    n_ref[...] = (carry * n + jax.lax.dot(
        w_k[None, :], k, preferred_element_type=jnp.float32)[0])[:, None]
    m_ref[0, 0] = m_out

    @pl.when(ci == pl.num_programs(2) - 1)
    def _done():
        c_fin_ref[0, 0] = C_ref[...]
        n_fin_ref[0, 0] = n_ref[...][:, 0]
        m_fin_ref[0, 0] = m_ref[0, 0]


def mlstm_chunked(q, k, v, ig, lf, *, chunk: int = 64,
                  interpret: bool = False):
    """q/k/v: [B, NH, S, DH] (k pre-scaled); ig/lf: [B, NH, S].
    Returns (h [B, NH, S, DH], (C [B,NH,DH,DH], n [B,NH,DH], m [B,NH]))."""
    b, nh, s, dh = q.shape
    c = min(chunk, s)
    assert s % c == 0, (s, c)
    grid = (b, nh, s // c)

    kernel = functools.partial(_kernel, chunk=c, dh=dh)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, c, dh), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, c, dh), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, c, dh), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, c), lambda b_, h_, ci: (b_, h_, ci)),
            pl.BlockSpec((1, 1, c), lambda b_, h_, ci: (b_, h_, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c, dh), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b_, h_, ci: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, dh), lambda b_, h_, ci: (b_, h_, 0)),
            pl.BlockSpec((1, 1), lambda b_, h_, ci: (b_, h_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, dh), q.dtype),
            jax.ShapeDtypeStruct((b, nh, dh, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, nh), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((dh, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, ig, lf)
    return h, (C, n, m)
