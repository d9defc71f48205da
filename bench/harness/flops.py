"""Operations and bytes a call needs, computed from its shapes.

Counts are of the work the algorithm requires, not of what a kernel
happens to do: padding rows, dead lanes and the unused part of a KV
block are left out, so a share of the roofline built from them cannot
pass 100% unless the time is counted short. A multiply-add is 2
operations. ``cfg`` is a configuration file's dict (``configs/*.json``).
"""
from __future__ import annotations

import numpy as np


def _dims(cfg: dict):
    hd = cfg["head_dim"] or cfg["d_model"] // cfg["num_heads"]
    return (cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
            cfg["num_kv_heads"], hd, cfg["d_ff"], cfg["vocab_size"])


def dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[name]


def block_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one decoder layer
    (q, k, v, o projections and the SwiGLU FFN)."""
    _, d, hq, hkv, hd, f, _ = _dims(cfg)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def token_flops(cfg: dict, attended, with_head) -> np.ndarray:
    """Model operations for tokens that attend to ``attended`` positions
    each (their own included), with the output head where ``with_head``."""
    L, d, hq, _, hd, _, v = _dims(cfg)
    attended = np.asarray(attended, np.float64)
    head = np.where(np.asarray(with_head, bool), 2.0 * d * v, 0.0)
    return (2.0 * block_matmul_params(cfg) * L
            + 4.0 * attended * hd * hq * L + head)


def paged_decode_cost(cfg: dict, attended, kv_dtype: str,
                      act_dtype: str) -> tuple:
    """(flops, bytes) of one paged decode attention call over all layers
    for live lanes that attend to ``attended`` positions each."""
    L, _, hq, hkv, hd, _, _ = _dims(cfg)
    a = np.asarray(attended, np.float64)
    lanes = a.size
    flops = 4.0 * hq * hd * a.sum() * L
    kv = 2.0 * hkv * hd * a.sum() * dtype_bytes(kv_dtype)
    qo = 2.0 * lanes * hq * hd * dtype_bytes(act_dtype)
    return flops, (kv + qo) * L


def paged_prefill_cost(cfg: dict, q_offset: int, chunk_len: int,
                       kv_dtype: str, act_dtype: str) -> tuple:
    """(flops, bytes) of one chunked-prefill attention call over all
    layers: rows at positions ``q_offset .. q_offset + chunk_len - 1``
    each attend causally to every position up to their own."""
    L, _, hq, hkv, hd, _, _ = _dims(cfg)
    ctx = q_offset + chunk_len
    attended = chunk_len * q_offset + chunk_len * (chunk_len + 1) / 2.0
    flops = 4.0 * hq * hd * attended * L
    kv = 2.0 * hkv * hd * ctx * dtype_bytes(kv_dtype)
    qo = 2.0 * hq * chunk_len * hd * dtype_bytes(act_dtype)
    return flops, (kv + qo) * L


def least_seconds(flops: float, nbytes: float, peak: dict,
                  flops_key: str = "bf16_flops") -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak[flops_key], nbytes / peak["hbm_bytes_per_s"])
