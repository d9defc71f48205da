"""Decoder-only LM covering the dense / moe / vlm families.

Layers are parameter-stacked on axis 0 and executed with ``jax.lax.scan`` so
the lowered HLO stays O(1) in depth (critical for 512-device dry-run compiles
and for pipeline-stage slicing in FHDP).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import blocks as B


def init_block(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": B.init_rmsnorm(cfg.d_model, cfg.dtype),
        "attn": B.init_attention(k1, cfg),
        "ln2": B.init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    if cfg.moe.num_experts:
        p["moe"] = B.init_moe(k2, cfg)
    else:
        p["ffn"] = B.init_mlp(k2, cfg)
    return p


def _factors_present(sub) -> bool:
    """True iff a LoRA factor subtree carries any actual {"A", "B"}
    factors (None everywhere = unadapted)."""
    if sub is None:
        return False
    leaves = jax.tree_util.tree_leaves(
        sub, is_leaf=lambda v: isinstance(v, dict) and "A" in v)
    return any(isinstance(leaf, dict) for leaf in leaves)


def apply_block(p: dict, x, cfg: ModelConfig, *, positions, cache=None,
                window=None, use_chunked=None, positions_contiguous=None,
                lora=None, lora_scale: float = 1.0):
    attn_lora = None if lora is None else lora.get("attn")
    ffn_lora = None if lora is None else lora.get("ffn")
    a, new_cache = B.attention(p["attn"], B.rms_norm(p["ln1"], x, cfg.norm_eps),
                               cfg, positions=positions, cache=cache,
                               window=window, use_chunked=use_chunked,
                               positions_contiguous=positions_contiguous,
                               lora=attn_lora, lora_scale=lora_scale)
    x = x + a
    h = B.rms_norm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        if _factors_present(None if lora is None else lora.get("moe")):
            raise NotImplementedError(
                "LoRA factors on MoE expert weights are not supported by "
                "the fused adapted forward; restrict LoRAConfig.targets "
                "to the attention/MLP projections")
        from repro.core import act_sharding
        r = act_sharding.current()
        if r is not None and r.mesh is not None \
                and "model" in getattr(r.mesh, "axis_names", ()):
            from repro.core.moe_ep import moe_block_ep
            f, aux = moe_block_ep(p["moe"], h, cfg, mesh=r.mesh,
                                  seq_sharded=r.seq_axis is not None)
        else:
            f, aux = B.moe_block(p["moe"], h, cfg)
    else:
        f, aux = B.mlp(p["ffn"], h, lora=ffn_lora, lora_scale=lora_scale), \
            jnp.zeros((), jnp.float32)
    return x + f, new_cache, aux


def init_latent(key, cfg: ModelConfig) -> dict:
    """Parameters of a latent-attention (MLA) model with experts: the
    leading dense layers stacked under ``dense``, the expert layers
    (held experts only, ``cfg.moe.held``) under ``blocks``. Only the
    paged engine runs this tree."""
    nd = cfg.moe.first_dense_layers
    ks = jax.random.split(key, 5)

    def layer(k, ffn):
        k1, k2 = jax.random.split(k)
        return {"ln1": B.init_rmsnorm(cfg.d_model, cfg.dtype),
                "attn": B.init_mla(k1, cfg),
                "ln2": B.init_rmsnorm(cfg.d_model, cfg.dtype),
                **ffn(k2)}

    params = {
        "embed": B.init_embedding(ks[0], cfg.vocab_size, cfg.d_model,
                                  cfg.dtype),
        "blocks": jax.vmap(lambda k: layer(
            k, lambda k2: {"moe": B.init_held_moe(k2, cfg)}))(
                jax.random.split(ks[1], cfg.num_layers - nd)),
        "ln_f": B.init_rmsnorm(cfg.d_model, cfg.dtype),
        "head": B.init_linear(ks[2], cfg.d_model, cfg.vocab_size, cfg.dtype),
    }
    if nd:
        params["dense"] = jax.vmap(lambda k: layer(
            k, lambda k2: {"ffn": B.init_mlp(k2, cfg)}))(
                jax.random.split(ks[3], nd))
    return params


def init(key, cfg: ModelConfig) -> dict:
    if cfg.latent:
        return init_latent(key, cfg)
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)
    params = {
        "embed": B.init_embedding(ks[1], cfg.vocab_size, cfg.d_model, cfg.dtype),
        "blocks": jax.vmap(lambda k: init_block(k, cfg))(layer_keys),
        "ln_f": B.init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = B.init_linear(ks[2], cfg.d_model, cfg.vocab_size,
                                       cfg.dtype)
    if cfg.prefix_tokens:  # vlm projector (stub ViT output -> d_model)
        params["projector"] = B.init_linear(ks[3], cfg.prefix_dim, cfg.d_model,
                                            cfg.dtype)
    return params


def _scan_blocks(params, x, cfg: ModelConfig, *, positions, caches=None,
                 window=None, remat=False, use_chunked=None,
                 positions_contiguous=None, lora=None, lora_scale=1.0):
    """Run the stacked block pytree over x. caches: stacked kv cache or None.

    ``lora`` is the layer-stacked factor subtree for ``params["blocks"]``
    (or None): scan slices the leading layer axis of each (A, B) factor
    exactly like the block weights, and None (unadapted) leaves are empty
    pytree nodes that cost nothing.
    """
    from repro.core.act_sharding import constrain

    def body(carry, layer):
        h = carry
        lp, lc, lf = layer
        out, new_cache, aux = apply_block(
            lp, h, cfg, positions=positions, cache=lc, window=window,
            use_chunked=use_chunked,
            positions_contiguous=positions_contiguous,
            lora=lf, lora_scale=lora_scale)
        return constrain(out), (new_cache, aux)

    fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body
    xs = (params["blocks"], caches, lora)
    x, (new_caches, auxs) = jax.lax.scan(fn, x, xs)
    return x, new_caches, auxs.sum()


def forward(params, cfg: ModelConfig, tokens, *, positions=None, caches=None,
            prefix_embeds=None, window=None, remat=False, use_chunked=None,
            logits_slice: Optional[int] = None, hidden_only: bool = False,
            lora=None, lora_scale: float = 1.0):
    """tokens: [B, S] int32. Returns (logits [B, S(, V)], new_caches, aux).

    ``lora``: optional factor pytree from ``distill.lora.init_lora`` (same
    structure as ``params``). Factors on the block stack run through the
    fused base+low-rank kernel without materializing merged weights; the
    base stays frozen, so grads w.r.t. ``lora`` are the adapter-only
    update federated distillation ships upstream.
    """
    lora_blocks = None
    if lora is not None:
        extra = {k: v for k, v in lora.items() if k != "blocks"}
        if _factors_present(extra):
            bad = sorted(k for k, v in extra.items() if _factors_present(v))
            raise NotImplementedError(
                f"LoRA factors outside the block stack are not supported "
                f"by the fused forward (got factors under {bad}); adapt "
                f"only block projections or fold with merge_lora instead")
        lora_blocks = lora.get("blocks")
    x = B.embed(params["embed"], tokens)
    npfx = 0
    if prefix_embeds is not None:
        pfx = B.linear(params["projector"], prefix_embeds.astype(x.dtype))
        x = jnp.concatenate([pfx, x], axis=1)
        npfx = pfx.shape[1]
    pos_contig = True if positions is None else None
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, new_caches, aux = _scan_blocks(params, x, cfg, positions=positions,
                                      caches=caches, window=window,
                                      remat=remat, use_chunked=use_chunked,
                                      positions_contiguous=pos_contig,
                                      lora=lora_blocks, lora_scale=lora_scale)
    x = B.rms_norm(params["ln_f"], x, cfg.norm_eps)
    if npfx:
        x = x[:, npfx:]
    if logits_slice is not None:
        x = x[:, -logits_slice:]
    if hidden_only:
        return x, new_caches, aux
    if cfg.tie_embeddings:
        logits = B.unembed(params["embed"], x)
    else:
        logits = B.linear(params["head"], x).astype(jnp.float32)
    return logits, new_caches, aux


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    return B.init_kv_cache(cfg, batch, cache_len, stacked=cfg.num_layers)
