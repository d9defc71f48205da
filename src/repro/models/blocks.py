"""Core transformer building blocks (pure-function JAX, dict pytree params).

Conventions:
  * params are nested dicts of jnp arrays; every ``init_*`` returns one.
  * activations flow as [batch, seq, d_model]; attention internals use
    [batch, heads, seq, head_dim].
  * all softmax/statistics in float32 regardless of param dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig

NEG_INF = -1e30

# optional Pallas kernel backend for self-attention (TPU fast path; on CPU
# the kernels run in interpret mode, so this is off by default here)
_KERNEL_BACKEND = False


def set_kernel_backend(on: bool) -> None:
    global _KERNEL_BACKEND
    _KERNEL_BACKEND = on


def kernel_backend() -> bool:
    return _KERNEL_BACKEND


# ---------------------------------------------------------------- norms ----
def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rms_norm(p: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------- rope ----
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: [B, H, S, D]; positions: [B, S] or [S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,S,half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention ----
def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    dt = cfg.dtype
    p = {
        "wq": (jax.random.normal(ks[0], (d, nq * hd)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, nkv * hd)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, nkv * hd)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (nq * hd, d)) * (nq * hd) ** -0.5).astype(dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd).transpose(0, 2, 1, 3)  # [B,N,S,D]


def _head_rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _contiguous_positions(positions) -> bool:
    """True iff ``positions`` is a trace-time constant describing the
    contiguous, non-negative layout the Pallas kernel's absolute-position
    masks assume (row i at q_offset + i, batch-uniform). Checked in
    numpy — jnp ops would be staged into the surrounding trace. Traced
    position arrays (packed sequences, -1 padding, per-example offsets)
    can't be checked, so they conservatively fall back to the XLA paths."""
    try:
        p = np.asarray(positions)
    except Exception:
        return False
    row = p if p.ndim == 1 else p[0]
    if p.ndim == 2 and not (p == row[None]).all():
        return False
    if row.size == 0 or row[0] < 0:
        return False
    return row.size == 1 or (np.diff(row) == 1).all()


def dense_mha(q, k, v, *, scale, q_pos, kv_pos, causal, window):
    """Reference attention. q:[B,Nq,Sq,D] k,v:[B,Nkv,Skv,D]."""
    b, nq, sq, d = q.shape
    nkv = k.shape[1]
    g = nq // nkv
    qg = q.reshape(b, nkv, g, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = kv_pos[None, :] >= 0
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return o.reshape(b, nq, sq, d)


def chunked_mha(q, k, v, *, scale, q_pos, kv_pos, causal, window,
                q_chunk=512, kv_chunk=1024):
    """Memory-efficient online-softmax attention (never materializes Sq x Skv).

    Single ``lax.scan`` over KV chunks; all Q rows are processed each
    iteration. This shape is deliberate for GSPMD: Q keeps its (sequence-
    over-``model``) sharding through the whole scan and K/V are gathered
    once per layer — a per-(q-chunk x kv-chunk) inner loop forces XLA to
    reshard Q and regather K/V on *every* iteration (measured 30x collective
    blow-up on the 16x16 mesh; see EXPERIMENTS.md §Perf).
    """
    b, nq, sq, d = q.shape
    nkv, skv = k.shape[1], k.shape[2]
    g = nq // nkv
    kc = min(kv_chunk, skv)
    while skv % kc:
        kc -= 1
    nkc = skv // kc

    from repro.core.act_sharding import constrain
    qg = constrain(q.reshape(b, nkv, g, sq, d), seq_dim=3)
    kb = k.reshape(b, nkv, nkc, kc, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, nkv, nkc, kc, d).transpose(2, 0, 1, 3, 4)
    kp = kv_pos.reshape(nkc, kc)

    m0 = constrain(jnp.full((b, nkv, g, sq), NEG_INF, jnp.float32), seq_dim=3)
    l0 = constrain(jnp.zeros((b, nkv, g, sq), jnp.float32), seq_dim=3)
    a0 = constrain(jnp.zeros((b, nkv, g, sq, d), jnp.float32), seq_dim=3)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(carry, inp):
        # rematted: the backward pass recomputes the [*, Sq, kc] scores of
        # one chunk at a time instead of storing them for every chunk
        m, l, acc = carry
        k_blk, v_blk, kpos = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_blk,
                       preferred_element_type=jnp.float32) * scale
        mask = kpos[None, :] >= 0
        if causal:
            mask = mask & (kpos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, kp))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, nq, sq, d).astype(q.dtype)


def _adapted_matmul(p: dict, name: str, x, lora, lora_scale: float):
    """``x @ p[name]`` with the leaf's LoRA factors fused in when the
    factor subtree carries them (None = unadapted). Routes through the
    fused base+low-rank Pallas matmul so the merged weight is never
    materialized on the fine-tuning hot path."""
    f = None if lora is None else lora.get(name)
    if f is None:
        return x @ p[name]
    from repro.distill.lora import lora_linear
    return lora_linear(x, p[name], f, lora_scale)


def attention(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
              positions: jnp.ndarray,
              cache: Optional[dict] = None,
              causal: bool = True,
              window: Optional[int] = None,
              cross_kv: Optional[tuple] = None,
              cross_pos: Optional[jnp.ndarray] = None,
              use_chunked: Optional[bool] = None,
              block_q: Optional[int] = None,
              block_k: Optional[int] = None,
              positions_contiguous: Optional[bool] = None,
              lora: Optional[dict] = None,
              lora_scale: float = 1.0):
    """Unified attention: self (train/prefill/decode w/ cache) or cross.

    ``block_q``/``block_k`` override the Pallas kernel tile sizes
    (default ``cfg.attn_block_q``/``cfg.attn_block_k``) so e.g. the FHDP
    step can tune tiles without bypassing autodiff.

    ``positions_contiguous`` asserts that positions are row i ->
    q_offset + i (the layout the Pallas kernel's masks assume). Model
    layers pass True when they built ``positions`` from ``jnp.arange``
    themselves; when None, concrete position arrays are value-checked
    and traced ones conservatively take the XLA paths.

    ``lora``: optional factor subtree matching this block's attention
    params ({"wq": {"A", "B"} | None, ...}); adapted projections run the
    fused base+low-rank kernel with ``lora_scale`` (= alpha/rank).

    Returns (output, new_cache).
    """
    b, s, _ = x.shape
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = _adapted_matmul(p, "wq", x, lora, lora_scale)
    if "bq" in p:
        q = q + p["bq"]
    q = _split_heads(q, nq, hd)
    if "q_norm" in p:
        q = _head_rmsnorm(q, p["q_norm"], cfg.norm_eps)

    if cross_kv is not None:
        k, v = cross_kv
        kv_pos = cross_pos
        new_cache = cache
        q = q  # no rope on cross-attention queries (enc-dec convention)
    else:
        k = _adapted_matmul(p, "wk", x, lora, lora_scale)
        vv = _adapted_matmul(p, "wv", x, lora, lora_scale)
        if "bk" in p:
            k, vv = k + p["bk"], vv + p["bv"]
        k = _split_heads(k, nkv, hd)
        v = _split_heads(vv, nkv, hd)
        if "k_norm" in p:
            k = _head_rmsnorm(k, p["k_norm"], cfg.norm_eps)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if cache is not None:
            k, v, kv_pos, new_cache = update_kv_cache(cache, k, v, positions)
        else:
            kv_pos = positions if positions.ndim == 1 else positions[0]
            new_cache = None

    scale = hd ** -0.5
    q_pos1 = positions if positions.ndim == 1 else positions[0]
    # Pallas fast path (TPU; interpret-mode on CPU): contiguous self-
    # attention without a ring cache maps 1:1 onto the flash kernel
    # (fwd AND bwd — uneven lengths are padded + masked inside it).
    if positions_contiguous is None:
        positions_contiguous = _contiguous_positions(positions)
    if (kernel_backend() and cross_kv is None and cache is None
            and hd % 8 == 0 and positions_contiguous):
        from repro.kernels import ops as kops
        o = kops.flash_attention_ad(q, k, v, scale, causal, window,
                                    int(k.shape[2] - s),
                                    block_q=block_q or cfg.attn_block_q,
                                    block_k=block_k or cfg.attn_block_k)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nq * hd)
        return (_adapted_matmul(p, "wo", o, lora,
                                lora_scale)).astype(x.dtype), new_cache
    if use_chunked is None:
        use_chunked = (s > 1024) and cross_kv is None
    if use_chunked:
        o = chunked_mha(q, k, v, scale=scale, q_pos=q_pos1, kv_pos=kv_pos,
                        causal=causal, window=window,
                        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    else:
        o = dense_mha(q, k, v, scale=scale, q_pos=q_pos1, kv_pos=kv_pos,
                      causal=causal, window=window)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, nq * hd)
    return (_adapted_matmul(p, "wo", o, lora,
                            lora_scale)).astype(x.dtype), new_cache


# ------------------------------------------------------------- kv cache ----
def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  stacked: int = 0) -> dict:
    """cache_len is the ring size (== window for sliding-window attention)."""
    shape = (batch, cfg.num_kv_heads, cache_len, cfg.hd)
    if stacked:
        shape = (stacked,) + shape
    pos_shape = (stacked, cache_len) if stacked else (cache_len,)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.full(pos_shape, -1, jnp.int32),
    }


def update_kv_cache(cache: dict, k_new, v_new, positions):
    """Write new K/V at ring positions; return full cache views + new cache.

    k_new: [B, Nkv, S_new, D]; positions: [S_new] or [B, S_new] (shared ring
    index — batch-uniform positions assumed).
    """
    ring = cache["k"].shape[2]
    pos1 = positions if positions.ndim == 1 else positions[0]
    idx = pos1 % ring
    k = cache["k"].at[:, :, idx, :].set(k_new.astype(cache["k"].dtype))
    v = cache["v"].at[:, :, idx, :].set(v_new.astype(cache["v"].dtype))
    pos = cache["pos"].at[idx].set(pos1)
    new_cache = {"k": k, "v": v, "pos": pos}
    return k, v, pos, new_cache


# ----------------------------------------------------------------- ffn -----
def init_mlp(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.dtype
    return {
        "wi": (jax.random.normal(ks[0], (d, f)) * d ** -0.5).astype(dt),
        "wg": (jax.random.normal(ks[1], (d, f)) * d ** -0.5).astype(dt),
        "wo": (jax.random.normal(ks[2], (f, d)) * f ** -0.5).astype(dt),
    }


def mlp(p: dict, x: jnp.ndarray, lora: Optional[dict] = None,
        lora_scale: float = 1.0) -> jnp.ndarray:
    h = jax.nn.silu(_adapted_matmul(p, "wg", x, lora, lora_scale)) \
        * _adapted_matmul(p, "wi", x, lora, lora_scale)
    return _adapted_matmul(p, "wo", h, lora, lora_scale)


# ----------------------------------------------------------------- moe -----
def init_moe(key, cfg: ModelConfig, experts: Optional[int] = None) -> dict:
    """The router over all experts and the weights of ``experts`` of them
    (all by default)."""
    d, e, de = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_expert
    n = e if experts is None else experts
    ks = jax.random.split(key, 4)
    dt = cfg.dtype
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (n, d, de)) * d ** -0.5).astype(dt),
        "wg": (jax.random.normal(ks[2], (n, d, de)) * d ** -0.5).astype(dt),
        "wo": (jax.random.normal(ks[3], (n, de, d)) * de ** -0.5).astype(dt),
    }


def moe_block(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """Top-k MoE with capacity-based scatter/gather dispatch.

    Never materializes a [T, E, cap] dispatch tensor (the one-hot einsum
    formulation is O(T*E*cap) memory — infeasible at 1M-token global
    batches). Tokens over capacity are dropped (contribute zero), standard
    GShard semantics. Returns (out, aux_loss).
    """
    b, s, d = x.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    t = b * s
    xt = x.reshape(t, d)
    logits = xt.astype(jnp.float32) @ p["router"]          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(t * k * cfg.moe.capacity_factor / e))
    # position of each (token, choice) within its expert queue via argsort
    # ranking — the one-hot-cumsum formulation materializes a [T*k, E]
    # integer tensor (hundreds of GB at 1M-token batches)
    flat_e = gate_idx.reshape(t * k)                        # [T*k]
    order = jnp.argsort(flat_e)                             # stable
    starts = jnp.searchsorted(flat_e[order], jnp.arange(e))  # [E]
    pos_sorted = jnp.arange(t * k) - starts[flat_e[order]]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)     # overflow -> pad

    # dispatch: scatter token activations into [E*cap(+pad), d]
    from repro.core.act_sharding import constrain_map
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    xk = jnp.repeat(xt, k, axis=0)                          # [T*k, d]
    buf = buf.at[slot].set(xk, mode="drop")
    # expert-parallel: expert dim over the tensor axis (all-to-all
    # dispatch), capacity slots over the data axis — leaving cap unsharded
    # replicates every expert's work across the data axis (measured 16x
    # FLOP inflation on the 16x16 mesh; EXPERIMENTS.md §Perf).
    expert_in = constrain_map(buf[:-1].reshape(e, cap, d),
                              {0: "seq", 1: "batch"})

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["wg"])) \
        * jnp.einsum("ecd,edf->ecf", expert_in, p["wi"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["wo"])     # [E, cap, d]
    expert_out = constrain_map(expert_out, {0: "seq", 1: "batch"})

    # combine: gather each (token, choice)'s expert output, weight, sum over k
    flat_out = jnp.concatenate(
        [expert_out.reshape(e * cap, d),
         jnp.zeros((1, d), expert_out.dtype)], axis=0)
    got = flat_out[slot].reshape(t, k, d)                   # [T, k, d]
    w = jnp.where(keep.reshape(t, k), gate_vals, 0.0).astype(got.dtype)
    out = jnp.einsum("tkd,tk->td", got, w,
                     preferred_element_type=jnp.float32).astype(x.dtype)

    # load-balance aux loss (Switch): E * sum(frac_tokens * frac_probs)
    me = probs.mean(0)                                      # [E]
    ce = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32).mean(0)
    aux = e * jnp.sum(me * ce) * cfg.moe.aux_loss_weight
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) \
        * cfg.moe.router_z_weight
    return out.reshape(b, s, d), aux + zloss


# ------------------------------------------------- held-expert layer -----
def init_held_moe(key, cfg: ModelConfig) -> dict:
    """``init_moe``'s router and the weights of the experts this chip
    holds (``cfg.moe.held`` of them), the selection bias where the router
    has one, and the shared experts as one SwiGLU."""
    m = cfg.moe
    k_moe, k_shared = jax.random.split(key)
    p = init_moe(k_moe, cfg, m.held)
    if m.selection_bias:
        p["bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared_experts:
        p["shared"] = init_mlp(k_shared, cfg.replace(
            d_ff=m.num_shared_experts * m.d_expert))
    return p


def route(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """The published router over all experts, in float32. x: [T, d].
    Returns (experts [T, k] int32, weights [T, k] float32): the top-k of
    the scores (plus the selection bias, which moves the choice and not
    the weights), renormalized where ``norm_topk``, times
    ``route_scale``. DeepSeek-V3's group-limited selection with
    ``n_group = topk_group = 1`` keeps every expert, so there are no
    groups here."""
    m = cfg.moe
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif m.score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown score_func {m.score_func!r}")
    choice = scores + p["bias"] if m.selection_bias else scores
    _, idx = jax.lax.top_k(choice, m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if m.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * m.route_scale


#: rows of one tile of the held-expert grouped matmul: every tile holds
#: rows of one expert only, so each expert's rows are padded to it
EXPERT_TILE = 16


#: the held experts' weights in an expert layer's parameters
EXPERT_WEIGHTS = ("wi", "wg", "wo")


def held_moe(p: dict, x: jnp.ndarray, cfg: ModelConfig, layer,
             valid=None):
    """Dropless MoE over the experts this chip holds, plus the shared
    experts. x: [B, S, d]; ``valid`` [B, S] bool marks the tokens to
    route (padding rows and dead lanes get no expert). ``p``'s expert
    weights (``EXPERT_WEIGHTS``) are the stacks of every MoE layer
    ``[L, E, ...]`` and ``layer`` (an int32 scalar) picks this layer's:
    a loop over the layers passes the stacks whole, which spares it a
    copy of each layer's experts; its router, bias and shared experts are
    this layer's.

    Every token is routed over all ``num_experts``; the (token, expert)
    assignments that land on experts ``[expert_offset, expert_offset +
    held)`` are sorted by expert, each expert's rows padded to
    ``EXPERT_TILE``, and computed by one grouped matmul over the held
    experts (``kernels.ops.moe_expert_ffn``). No assignment is dropped.
    What the experts held elsewhere would add is not computed here; on
    one chip the layer runs without the exchange that would bring it.
    Returns (out [B, S, d], stats int32 [3]: assignments on held
    experts, held experts hit, most rows on one held expert)."""
    from repro.kernels import ops as kops

    m = cfg.moe
    b, s, d = x.shape
    t, k, eh = b * s, m.top_k, m.held
    xt = x.reshape(t, d)
    with jax.named_scope("router"):
        idx, w = route(p, xt, cfg)
    with jax.named_scope("experts"):
        local = idx - m.expert_offset
        held = (local >= 0) & (local < eh)
        if valid is not None:
            held &= valid.reshape(t, 1)
        flat = jnp.where(held, local, eh).reshape(t * k)   # eh: not here
        sizes = jnp.zeros((eh + 1,), jnp.int32).at[flat].add(1)[:eh]
        tiles = -(-sizes // EXPERT_TILE)
        tile_end = jnp.cumsum(tiles)
        start = (tile_end - tiles) * EXPERT_TILE            # padded starts
        # rank of each assignment within its expert (stable by token)
        order = jnp.argsort(flat, stable=True)
        fs = flat[order]
        first = jnp.searchsorted(fs, jnp.arange(eh + 1, dtype=fs.dtype))
        rank = jnp.arange(t * k) - first[fs]
        n_rows = t * min(k, eh) + eh * (EXPERT_TILE - 1)
        n_rows = -(-n_rows // EXPERT_TILE) * EXPERT_TILE
        row_sorted = jnp.where(fs < eh,
                               start[jnp.minimum(fs, eh - 1)] + rank, n_rows)
        row = jnp.zeros((t * k,), jnp.int32).at[order].set(row_sorted)
        xs = jnp.zeros((n_rows, d), x.dtype).at[row].set(
            jnp.repeat(xt, k, axis=0), mode="drop")
        n_tiles = n_rows // EXPERT_TILE
        tile_expert = jnp.minimum(
            jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
            eh - 1).astype(jnp.int32)
        ys = kops.moe_expert_ffn(xs, tile_expert, tile_end[-1:],
                                 jnp.reshape(layer, (1,)),
                                 *(p[n] for n in EXPERT_WEIGHTS),
                                 tile=EXPERT_TILE)
        got = ys[jnp.minimum(row, n_rows - 1)].reshape(t, k, d)
        wk = jnp.where(held, w, 0.0)
        out = jnp.einsum("tkd,tk->td", got.astype(jnp.float32), wk)
        stats = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
    if "shared" in p:
        with jax.named_scope("shared"):
            out = out + mlp(p["shared"], xt).astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, d), stats.astype(jnp.int32)


# ------------------------------------------------ latent attention (MLA) --
def init_mla(key, cfg: ModelConfig) -> dict:
    """MLA weights in the published layout: ``wq`` [d, H * (nope +
    rope)], ``wkva`` [d, kv_lora_rank + rope] (the latent and the shared
    rotary key), the latent's RMSNorm, ``wkvb`` [kv_lora_rank, H * (nope
    + v)] (each head's key and value from the latent), ``wo``."""
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    if a.q_lora_rank:
        raise NotImplementedError("MLA with a query LoRA (q_lora_rank > 0)")
    ks = jax.random.split(key, 4)
    dt = cfg.dtype

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in ** -0.5).astype(dt)

    r = a.kv_lora_rank
    return {"wq": w(ks[0], (d, h * a.qk_head_dim), d),
            "wkva": w(ks[1], (d, a.row), d),
            "kv_norm": init_rmsnorm(r, dt),
            "wkvb": w(ks[2], (r, h * (a.qk_nope_head_dim + a.v_head_dim)), r),
            "wo": w(ks[3], (h * a.v_head_dim, d), h * a.v_head_dim)}


def _deinterleave(x):
    """[..., 2n] with rotary pairs at lanes (2i, 2i + 1) -> the two-halves
    layout ``rope`` rotates: even lanes, then odd lanes."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def mla_absorbed(p: dict, h: jnp.ndarray, positions, cfg: ModelConfig):
    """Latent-space query and cache row of MLA with the key up-projection
    absorbed into the query. h: [B, S, d] normed; positions [B, S].

    Returns (q [B, S, H, r + rope]: ``q_nope_h · W_kvb^{k,h}ᵀ`` beside the
    rotated ``q_pe_h``; row [B, S, r + rope]: the normed latent beside the
    rotated shared key). ``q · row`` is each head's published score
    ``q_nope·k_nope + q_pe·k_pe``."""
    a, nh = cfg.mla, cfg.num_heads
    b, s, _ = h.shape
    r, nope = a.kv_lora_rank, a.qk_nope_head_dim
    q = (h @ p["wq"]).reshape(b, s, nh, a.qk_head_dim)
    kva = h @ p["wkva"]                                  # [B, S, r + rope]
    c = rms_norm(p["kv_norm"], kva[..., :r], a.kv_norm_eps)
    q_pe, k_pe = q[..., nope:], kva[..., None, r:]       # [B, S, 1, rope]
    if a.rope_interleave:
        q_pe, k_pe = _deinterleave(q_pe), _deinterleave(k_pe)
    q_pe = rope(q_pe.transpose(0, 2, 1, 3), positions,
                cfg.rope_theta).transpose(0, 2, 1, 3)
    k_pe = rope(k_pe.transpose(0, 2, 1, 3), positions,
                cfg.rope_theta)[:, 0]                    # [B, S, rope]
    with jax.named_scope("latent_absorb"):
        wk = p["wkvb"].reshape(r, nh, -1)[..., :nope]    # [r, H, nope]
        q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope], wk,
                           preferred_element_type=jnp.float32)
    return (jnp.concatenate([q_lat.astype(h.dtype), q_pe], axis=-1),
            jnp.concatenate([c, k_pe], axis=-1))


def mla_output(p: dict, o_lat: jnp.ndarray, cfg: ModelConfig):
    """Heads' latent outputs [B, S, H, r] -> [B, S, d]: each head's value
    up-projection ``W_kvb^{v,h}``, then ``wo``."""
    a, nh = cfg.mla, cfg.num_heads
    b, s = o_lat.shape[:2]
    wv = p["wkvb"].reshape(a.kv_lora_rank, nh, -1)[..., a.qk_nope_head_dim:]
    with jax.named_scope("latent_absorb"):
        o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(wv.dtype), wv,
                       preferred_element_type=jnp.float32)
    return o.astype(p["wo"].dtype).reshape(b, s, nh * a.v_head_dim) @ p["wo"]


# ------------------------------------------------------------ embedding ----
def init_embedding(key, vocab: int, d: int, dtype) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d)) * d ** -0.5).astype(dtype)}


def embed(p: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return p["table"][tokens]


def unembed(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("bsd,vd->bsv", x, p["table"],
                      preferred_element_type=jnp.float32)


def init_linear(key, din: int, dout: int, dtype, bias: bool = False) -> dict:
    p = {"w": (jax.random.normal(key, (din, dout)) * din ** -0.5).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((dout,), dtype)
    return p


def linear(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y
