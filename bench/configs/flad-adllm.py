"""Plain reference of the flad-adllm decoder, and its weights from a seed.

Written from the model's description (a LLaMA-style decoder: RMSNorm
before attention and FFN, grouped-query attention with rotary position
embedding on the two halves of each head, SwiGLU FFN, untied output
head), in straightforward ``jax.numpy``. It imports nothing of the
program under test. The weight layout is the program's parameter tree,
which is its interface: the benchmark makes the weights here and hands
the same arrays to the program and to this reference.

``mode="f32"``: every matmul in float32 at ``highest`` precision.
``mode="fp8"``: the control. Each projection, FFN and head matmul takes
its operands rounded to float8 e4m3 (weights scaled per tensor,
activations per row, both to the format's largest value), the step
below the configuration's bfloat16 that a later change might try.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def dims(cfg: dict):
    hd = cfg["head_dim"] or cfg["d_model"] // cfg["num_heads"]
    return (cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
            cfg["num_kv_heads"], hd, cfg["d_ff"], cfg["vocab_size"])


def init_params(cfg: dict, key):
    """Weights in the served dtype, N(0, 1/fan_in), norms at one; jit it."""
    L, d, hq, hkv, hd, f, v = dims(cfg)
    dt = jnp.dtype(cfg["dtype"])
    ks = jax.random.split(key, 9)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    ones = lambda *s: jnp.ones(s, dt)
    return {
        "embed": {"table": w(ks[0], (v, d), d)},
        "blocks": {
            "ln1": {"scale": ones(L, d)},
            "attn": {"wq": w(ks[1], (L, d, hq * hd), d),
                     "wk": w(ks[2], (L, d, hkv * hd), d),
                     "wv": w(ks[3], (L, d, hkv * hd), d),
                     "wo": w(ks[4], (L, hq * hd, d), hq * hd)},
            "ln2": {"scale": ones(L, d)},
            "ffn": {"wi": w(ks[5], (L, d, f), d),
                    "wg": w(ks[6], (L, d, f), d),
                    "wo": w(ks[7], (L, f, d), f)},
        },
        "ln_f": {"scale": ones(d)},
        "head": {"w": w(ks[8], (d, v), d)},
    }


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(mode):
    hi = jax.lax.Precision.HIGHEST

    def mm(x, w):
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        if mode == "fp8":
            x, w = _fp8(x, -1), _fp8(w, None)
        return jnp.matmul(x, w, precision=hi)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, D]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _logits_at(params, tokens, rows, *, cfg_items, mode):
    cfg = dict(cfg_items)
    L, d, hq, hkv, hd, f, v = dims(cfg)
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    mm = _matmul(mode)
    hi = jax.lax.Precision.HIGHEST
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = _rope(mm(h, a["wq"]).reshape(s, hq, hd), pos, theta)
        k = _rope(mm(h, a["wk"]).reshape(s, hkv, hd), pos, theta)
        vv = mm(h, a["wv"]).reshape(s, hkv, hd)
        q = q.reshape(s, hkv, hq // hkv, hd)
        sc = jnp.einsum("qkgd,pkd->kgqp", q, k, precision=hi) * hd ** -0.5
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqp,pkd->qkgd", pr, vv, precision=hi)
        x = x + mm(o.reshape(s, hq * hd), a["wo"])
        fp = p["ffn"]
        h = _rms(x, p["ln2"]["scale"], eps)
        x = x + mm(jax.nn.silu(mm(h, fp["wg"])) * mm(h, fp["wi"]), fp["wo"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
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
