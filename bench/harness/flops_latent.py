"""Operations and bytes of a latent-attention (MLA) model with experts
held here, computed from its shapes (``configs/moonlight-16b-a3b.json``'s
keys).

As in ``flops.py``, counts are of the work the algorithm needs, not of
what a kernel happens to do: dead lanes, padding rows, the zero lanes
that pad a latent row to whole tiles and the rows that pad an expert's
group to whole tiles are left out, so a share of the roofline built from
them cannot pass 100% unless the time is counted short. A multiply-add is
2 operations.

Attention is counted in the absorbed form the program serves: a query in
the latent (``r`` + rope lanes) scores each cached row, and the output is
the weighted sum of the rows' first ``r`` lanes, so each attended
position costs ``2·H·((r + rope) + r)`` operations and its row, read
once for all heads, ``(r + rope)`` values.
"""
from __future__ import annotations

import numpy as np

from bench.harness.flops import dtype_bytes


def dims(cfg: dict) -> dict:
    nd = cfg["first_k_dense_replace"]
    return {"L": cfg["num_hidden_layers"], "nd": nd,
            "nm": cfg["num_hidden_layers"] - nd, "d": cfg["hidden_size"],
            "h": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "f": cfg["intermediate_size"],
            "de": cfg["moe_intermediate_size"], "e": cfg["router_experts"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "v": cfg["vocab_size"]}


def attention_position_flops(cfg: dict) -> float:
    """Operations of one head-batch of absorbed attention over one
    attended position in one layer: scores over the row and the sum of
    its latent lanes, for every head."""
    n = dims(cfg)
    return 2.0 * n["h"] * ((n["r"] + n["rope"]) + n["r"])


def row_bytes(cfg: dict, kv_dtype: str) -> float:
    """Bytes of one token's cached row in one layer: the latent and its
    rotary key."""
    n = dims(cfg)
    return (n["r"] + n["rope"]) * dtype_bytes(kv_dtype)


def latent_decode_cost(cfg: dict, attended, kv_dtype: str,
                       act_dtype: str) -> tuple:
    """(flops, bytes) of the paged decode kernel over all layers for live
    lanes that attend to ``attended`` positions each: every row read
    once, each lane's latent query in and latent output out."""
    n = dims(cfg)
    a = np.asarray(attended, np.float64)
    flops = attention_position_flops(cfg) * a.sum() * n["L"]
    qo = a.size * n["h"] * ((n["r"] + n["rope"]) + n["r"]) * dtype_bytes(
        act_dtype)
    return flops, (row_bytes(cfg, kv_dtype) * a.sum() + qo) * n["L"]


def latent_prefill_cost(cfg: dict, q_offset: int, chunk_len: int,
                        kv_dtype: str, act_dtype: str) -> tuple:
    """(flops, bytes) of one chunked-prefill kernel call over all layers:
    rows at positions ``q_offset .. q_offset + chunk_len - 1`` each attend
    causally to every position up to their own; the context's rows are
    read once."""
    n = dims(cfg)
    ctx = q_offset + chunk_len
    attended = chunk_len * q_offset + chunk_len * (chunk_len + 1) / 2.0
    flops = attention_position_flops(cfg) * attended * n["L"]
    qo = chunk_len * n["h"] * ((n["r"] + n["rope"]) + n["r"]) * dtype_bytes(
        act_dtype)
    return flops, (row_bytes(cfg, kv_dtype) * ctx + qo) * n["L"]


def expert_flops(cfg: dict, assignments) -> float:
    """Operations of the held experts' SwiGLUs for ``assignments``
    (token, held expert) pairs."""
    n = dims(cfg)
    return 6.0 * n["d"] * n["de"] * float(np.sum(assignments))


def moe_expert_cost(cfg: dict, assignments: int, experts_hit: int,
                    w_dtype: str, act_dtype: str) -> tuple:
    """(flops, bytes) of one grouped-matmul call: the assignments' rows in
    and out, and the weights of the held experts that have rows, each
    read once."""
    n = dims(cfg)
    w = 3.0 * n["d"] * n["de"] * dtype_bytes(w_dtype) * experts_hit
    rows = 2.0 * n["d"] * dtype_bytes(act_dtype) * assignments
    return expert_flops(cfg, assignments), w + rows


def token_flops(cfg: dict, attended, with_head) -> np.ndarray:
    """Model operations of tokens attending to ``attended`` positions each
    (their own included), with the output head where ``with_head``, but
    for the routed experts (``expert_flops`` of the assignments that
    landed here): every layer's MLA projections in the absorbed form
    (queries, latent and rotary key, each head's query into the latent
    and its latent output back out, the output projection) and
    attention; the dense layers' SwiGLU; the expert layers' router over
    all experts and shared experts."""
    n = dims(cfg)
    d, h, r = n["d"], n["h"], n["r"]
    proj = (d * h * (n["nope"] + n["rope"]) + d * (r + n["rope"])
            + h * n["nope"] * r + h * r * n["dv"] + h * n["dv"] * d)
    attended = np.asarray(attended, np.float64)
    per_token = (2.0 * proj * n["L"] + 6.0 * d * n["f"] * n["nd"]
                 + (2.0 * d * n["e"] + 6.0 * d * n["fs"]) * n["nm"])
    att = attention_position_flops(cfg) * attended * n["L"]
    head = np.where(np.asarray(with_head, bool), 2.0 * d * n["v"], 0.0)
    return per_token + att + head


def param_count(cfg: dict) -> int:
    """Parameters held here: embedding and head, the layers' MLA, the
    dense layers' SwiGLU, and the expert layers' router, selection bias,
    held experts and shared experts."""
    n = dims(cfg)
    d, h, r = n["d"], n["h"], n["r"]
    mla = (d * h * (n["nope"] + n["rope"]) + d * (r + n["rope"]) + r
           + r * h * (n["nope"] + n["dv"]) + h * n["dv"] * d + 2 * d)
    moe = (d * n["e"] + n["e"] + cfg["n_routed_experts"] * 3 * d * n["de"]
           + 3 * d * n["fs"])
    return (2 * n["v"] * d + d + n["L"] * mla + n["nd"] * 3 * d * n["f"]
            + n["nm"] * moe)
