"""Plain reference of Moonlight-16B-A3B (DeepSeek-V3's block), and its
weights from a seed.

Written from the published equations (``transformers``' ``deepseek_v3``
modeling and configuration files: the router, the MoE with shared
experts, multi-head latent attention, ``first_k_dense_replace``) in
straightforward ``jax.numpy``, in the published, non-absorbed form: each
head's key and value are up-projected from the normed latent, and the
rotary key is shared by the heads. It imports nothing of the program
under test. The weight layout is the program's parameter tree, which is
its interface: the benchmark makes the weights here and hands the same
arrays to the program and to this reference.

Expert parallelism: the file's ``n_routed_experts`` experts are held here
(experts ``expert_offset`` onwards of ``router_experts``). The router
scores all ``router_experts``; only the held experts' part of the MoE
output is computed, as on the chip that holds them, and what the others
would add is left out here too. The shared experts count whole.

``mode="f32"``: every matmul in float32 at ``highest`` precision. The
weights stay in their served dtype and each layer's are cast as the
layer runs, so no float32 copy of the whole model is ever held.
``mode="fp8"``: the control. Each projection, FFN, expert and head matmul
takes its operands rounded to float8 e4m3 (weights scaled per matrix,
activations per row); the router, attention and norms stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def dims(cfg: dict) -> dict:
    n_dense = cfg["first_k_dense_replace"]
    return {
        "nd": n_dense, "nm": cfg["num_hidden_layers"] - n_dense,
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "f": cfg["intermediate_size"], "de": cfg["moe_intermediate_size"],
        "e": cfg["router_experts"], "eh": cfg["n_routed_experts"],
        "off": cfg["expert_offset"], "k": cfg["num_experts_per_tok"],
        "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "v": cfg["vocab_size"]}


def init_params(cfg: dict, key):
    """Weights in the served dtype, N(0, 1/fan_in), norms at one, the
    selection bias N(0, 0.1^2) in float32; jit it."""
    n = dims(cfg)
    d, h, r = n["d"], n["h"], n["r"]
    dt = jnp.dtype(cfg["dtype"])
    ks = iter(jax.random.split(key, 32))

    def w(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def layers(L, ffn):
        return {
            "ln1": {"scale": jnp.ones((L, d), dt)},
            "attn": {"wq": w((L, d, h * (n["nope"] + n["rope"])), d),
                     "wkva": w((L, d, r + n["rope"]), d),
                     "kv_norm": {"scale": jnp.ones((L, r), dt)},
                     "wkvb": w((L, r, h * (n["nope"] + n["dv"])), r),
                     "wo": w((L, h * n["dv"], d), h * n["dv"])},
            "ln2": {"scale": jnp.ones((L, d), dt)},
            **ffn(L)}

    def dense(L):
        return {"ffn": {"wi": w((L, d, n["f"]), d), "wg": w((L, d, n["f"]), d),
                        "wo": w((L, n["f"], d), n["f"])}}

    def moe(L):
        eh, de, fs = n["eh"], n["de"], n["fs"]
        return {"moe": {
            "router": jax.random.normal(next(ks), (L, d, n["e"]),
                                        jnp.float32) * d ** -0.5,
            "bias": 0.1 * jax.random.normal(next(ks), (L, n["e"]),
                                            jnp.float32),
            "wi": w((L, eh, d, de), d), "wg": w((L, eh, d, de), d),
            "wo": w((L, eh, de, d), de),
            "shared": {"wi": w((L, d, fs), d), "wg": w((L, d, fs), d),
                       "wo": w((L, fs, d), fs)}}}

    params = {
        "embed": {"table": w((n["v"], d), d)},
        "blocks": layers(n["nm"], moe),
        "ln_f": {"scale": jnp.ones((d,), dt)},
        "head": {"w": w((d, n["v"]), d)},
    }
    if n["nd"]:
        params["dense"] = layers(n["nd"], dense)
    return params


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(mode):
    hi = jax.lax.Precision.HIGHEST

    def mm(x, w):
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        if mode == "fp8":
            x, w = _fp8(x, -1), _fp8(w, (-2, -1))
        return jnp.matmul(x, w, precision=hi)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope_interleaved(x, pos, theta):
    """x [S, ..., D]: rotary pairs at lanes (2i, 2i + 1), as DeepSeek-V3
    with ``rope_interleave``: de-interleave, then rotate the halves."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.reshape((-1,) + (1,) * (x.ndim - 2) + (1,)).astype(
        jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _swiglu(mm, p, x):
    return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wi"]), p["wo"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _logits_at(params, tokens, rows, *, cfg_items, mode):
    cfg = dict(cfg_items)
    n = dims(cfg)
    h, r, nope, rope = n["h"], n["r"], n["nope"], n["rope"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = _matmul(mode)
    hi = jax.lax.Precision.HIGHEST
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def attention(x, a):
        q = mm(x, a["wq"]).reshape(s, h, nope + rope)
        kva = mm(x, a["wkva"])
        c = _rms(kva[:, :r], a["kv_norm"]["scale"], cfg["kv_norm_eps"])
        kv = mm(c, a["wkvb"]).reshape(s, h, nope + n["dv"])
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe = _rope_interleaved(q[..., nope:], pos, theta)     # [S, H, rope]
        k_pe = _rope_interleaved(kva[:, r:], pos, theta)        # [S, rope]
        sc = (jnp.einsum("qhn,phn->hqp", q[..., :nope], k_nope, precision=hi)
              + jnp.einsum("qhr,pr->hqp", q_pe, k_pe, precision=hi))
        sc = sc * (nope + rope) ** -0.5
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqp,phv->qhv", pr, v, precision=hi)
        return mm(o.reshape(s, h * n["dv"]), a["wo"])

    def moe(x, p):
        logits = jnp.matmul(x, p["router"], precision=hi)       # all experts
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["bias"], n["k"])
        wk = jnp.take_along_axis(scores, idx, -1)
        if cfg["norm_topk_prob"]:
            wk = wk / (wk.sum(-1, keepdims=True) + 1e-20)
        wk = wk * cfg["routed_scaling_factor"]
        # weight of each held expert for each token (0 where not chosen)
        held = jnp.arange(n["eh"]) + n["off"]
        gate = jnp.sum(jnp.where(idx[:, :, None] == held, wk[:, :, None],
                                 0.0), axis=1)                   # [S, Eh]

        def expert(acc, e):
            pe = {k: p[k][e] for k in ("wi", "wg", "wo")}
            return acc + gate[:, e, None] * _swiglu(mm, pe, x), None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                              jnp.arange(n["eh"]))
        return out + _swiglu(mm, p["shared"], x)

    def layer(ffn):
        def body(x, p):
            x = x + attention(_rms(x, p["ln1"]["scale"], eps), p["attn"])
            return x + ffn(_rms(x, p["ln2"]["scale"], eps), p), None
        return body

    if n["nd"]:
        x, _ = jax.lax.scan(layer(lambda y, p: _swiglu(mm, p["ffn"], y)), x,
                            params["dense"])
    x, _ = jax.lax.scan(layer(lambda y, p: moe(y, p["moe"])), x,
                        params["blocks"])
    x = _rms(x[rows], params["ln_f"]["scale"], eps)
    return mm(x, params["head"]["w"])


def logits_at(params, cfg: dict, tokens, rows, mode: str = "f32"):
    """Logits [len(rows), V] of ``tokens`` [S] at positions ``rows``.
    Later positions never reach earlier ones (causal), so ``tokens`` may
    carry padding after the last row asked for."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    return _logits_at(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32), cfg_items=items,
                      mode=mode)
