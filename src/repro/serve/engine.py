"""Paged-cache prefill/decode forward for the decoder-only LM family.

Splits :func:`repro.models.lm.forward` at the KV boundary so decode runs
against the block pools of :mod:`repro.serve.kvcache` instead of a
per-request contiguous cache:

  * **prefill** reuses the contiguous machinery unchanged — one request
    at a time, prompt padded to a fixed ``max_context`` bucket (one jit
    trace), causal masking keeps the padded tail out of every real
    position's attention — and returns the last true token's logits plus
    the layer-stacked K/V to scatter into pool blocks;
  * **decode** re-implements the block walk as a ``lax.scan`` over the
    layers whose carry holds the whole layer-stacked pools: embed ->
    rms/qkv/rope (positions = per-request context lengths) -> write the
    token's K/V row into its physical block at ``(layer, block,
    offset)`` -> the paged Pallas decode kernel
    (:func:`repro.kernels.ops.paged_decode_attention`, handed the whole
    stacks and the layer index) -> wo/ffn. All ``slots`` batch lanes run
    the projections every step; dead lanes point at the null block and
    attend to an empty context, so the kernel copies none of their pages
    and writes zeros for them.

Every program that takes the pools donates them (``donate_argnums``):
the scan's carry aliases the caller's buffers, XLA writes each layer's
rows in place, and nothing slices, relayouts or writes back a layer of
a pool, so no launch copies or allocates one. A caller must rebind the
pools a call returns; the ones it passed are deleted.

The numerics match the contiguous path op for op (same rope-after-norm
order, float32 softmax statistics), which is what the paged-vs-contiguous
equivalence test in ``tests/test_serve.py`` pins down.

Latent attention (MLA, ``cfg.latent``) runs the same two walks in its
absorbed form: each layer writes one latent row per token (the normed
latent beside the rotated shared key) into a one-head latent pool, each
head's query is taken into the latent (``attention/latent_absorb``), the
paged kernels attend over the rows with the latent's lanes as values,
and the heads' value up-projections follow. Leading dense layers
(``moe.first_dense_layers``) run as a walk of their own before the walk
over the expert layers; both carry the one latent stack of every layer.

MoE layers on this path are the dropless held-expert layer
(:func:`repro.models.blocks.held_moe`: ``mlp/router``, ``mlp/experts``,
``mlp/shared``), never the capacity-routed ``moe_block`` of training.
For such a model both programs also return an int32 ``[L_moe, 3]`` of
the expert load per layer (assignments on held experts, held experts
hit, most rows on one held expert); ``decode`` and ``prefill_chunk``
keep it on the device as ``moe_stats`` for the scheduler to read with
the sampled tokens.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops as kops
from repro.models import blocks as B
from repro.models import lm
from repro.serve import kvcache as KC

_PAGED_FAMILIES = ("dense", "moe")


def _layer_xs(blocks: dict):
    """What a scan over the layers walks: (blocks, each layer's index).
    For a model with experts the held experts' weights are taken out of
    ``blocks`` and returned beside as the whole stacks ``[L, E, ...]``.
    The paged kernels pick their layer of the pools (which ride the
    scan's carry) and the grouped matmul its layer's experts from the
    stacks by that index, so the scan slices and copies none of them."""
    idx = jnp.arange(jax.tree.leaves(blocks)[0].shape[0], dtype=jnp.int32)
    if "moe" not in blocks:
        return (blocks, idx), None
    moe = blocks["moe"]
    stacks = {n: moe[n] for n in B.EXPERT_WEIGHTS}
    blocks = dict(blocks, moe={n: w for n, w in moe.items()
                               if n not in stacks})
    return (blocks, idx), stacks


class PagedEngine:
    """Jitted paged prefill/decode pair for one (cfg, spec, slots)."""

    def __init__(self, cfg: ModelConfig, spec: KC.PagedCacheSpec, *,
                 max_context: int, slots: int):
        if cfg.family not in _PAGED_FAMILIES:
            raise NotImplementedError(
                f"paged serving covers the LM families {_PAGED_FAMILIES}; "
                f"{cfg.family!r} keeps the legacy contiguous path")
        if cfg.window is not None:
            raise NotImplementedError(
                "paged serving assumes full causal attention (window=None)")
        if cfg.moe.first_dense_layers and not cfg.latent:
            raise NotImplementedError(
                "leading dense layers are served for latent-attention "
                "models only")
        if max_context > spec.max_tokens_per_req:
            raise ValueError(
                f"max_context {max_context} exceeds the table capacity "
                f"{spec.max_tokens_per_req} tokens")
        self.cfg = cfg
        self.spec = spec
        self.max_context = int(max_context)
        self.slots = int(slots)
        self._prefill = jax.jit(self._prefill_impl)
        # the pools are donated: each program writes them in place
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                      donate_argnums=1)
        self._decode = jax.jit(self._decode_impl, donate_argnums=1)
        self._verify = jax.jit(self._verify_impl, donate_argnums=1)
        self._write = jax.jit(functools.partial(KC.write_prefill, spec=spec),
                              donate_argnums=0)
        self._copy_block = jax.jit(self._copy_block_impl, donate_argnums=0)
        self._restore = jax.jit(KC.scatter_rows, donate_argnums=0)
        #: per-layer expert load of the last ``decode`` / ``prefill_chunk``
        #: (int32 [L_moe, 3] on the device); None for a model without
        #: experts
        self.moe_stats = None

    # ---- pools --------------------------------------------------------
    def init_pools(self) -> Dict:
        return KC.init_pools(self.cfg, self.spec)

    # ---- prefill ------------------------------------------------------
    def _prefill_impl(self, params, tokens, length):
        """tokens: [1, max_context] int32 (padded); length: scalar int32.
        Returns (last-token logits [1, V], k [L, Hkv, Smax, D], v)."""
        cfg = self.cfg
        if cfg.latent or cfg.moe.num_experts:
            raise NotImplementedError(
                "monolithic prefill runs the training forward (capacity-"
                "routed experts, no latent pool); serve a latent-attention "
                "or expert model with prefill='chunked'")
        caches = lm.init_cache(cfg, 1, self.max_context)
        x, new_caches, _ = lm.forward(params, cfg, tokens, caches=caches,
                                      hidden_only=True)
        h = x[:, length - 1]                       # [1, d], true last token
        if cfg.tie_embeddings:
            logits = B.unembed(params["embed"], h[:, None])[:, 0]
        else:
            logits = B.linear(params["head"], h).astype(jnp.float32)
        k = new_caches["k"][:, 0]                  # [L, Hkv, Smax, D]
        v = new_caches["v"][:, 0]
        return logits, k, v

    def prefill(self, params, tokens, length) -> Tuple:
        return self._prefill(params, tokens, length)

    def write_prefill(self, pools, k_layers, v_layers, table_row) -> Dict:
        return self._write(pools, k_layers=k_layers, v_layers=v_layers,
                           table_row=table_row)

    # ---- chunked prefill ---------------------------------------------
    def _prefill_chunk_impl(self, params, pools, tokens, table, q_offset,
                            chunk_len):
        """One prompt chunk of ONE request straight into its pool blocks.

        tokens: [C] int32 (rows past ``chunk_len`` are padding); table:
        [T] int32 logical->physical; q_offset/chunk_len: scalar int32
        (chunk covers absolute positions [q_offset, q_offset +
        chunk_len)). No ``[L, Hkv, Smax, D]`` staging buffer and no
        max_context padding: each layer scatters the chunk's K/V into the
        pool (padding rows target the null block) and attends to the
        prior context *plus itself* through the block table via the
        chunked-prefill Pallas kernel. Returns (logits [1, V] of the
        chunk's last true row — only meaningful on the final chunk — and
        the updated pools). Mirrors ``_decode_impl`` op for op so chunked
        and monolithic prefill agree bit-for-bit in greedy streams.
        Its phases carry the same ``jax.named_scope`` names as
        ``_decode_impl``'s. A model with experts also returns their load
        (int32 [L_moe, 3])."""
        cfg, spec = self.cfg, self.spec
        nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        c = tokens.shape[0]
        scale = hd ** -0.5

        x = B.embed(params["embed"], tokens[None])         # [1, C, d]
        pos = q_offset + jnp.arange(c, dtype=jnp.int32)    # absolute
        positions = pos[None]                              # [1, C]
        rows = jnp.arange(c, dtype=jnp.int32)
        blk = pos // spec.block_size
        phys = jnp.where(rows < chunk_len, table[blk], 0)  # [C]
        off = pos % spec.block_size
        valid = (rows < chunk_len)[None]                   # [1, C]
        if cfg.latent:
            def attend(q, pool, i):                        # [1, C, H, row]
                o = kops.paged_prefill_attention(
                    q[0].transpose(1, 0, 2), pool, None, table, q_offset,
                    q_offset + chunk_len, layer=i,
                    scale=cfg.mla.qk_head_dim ** -0.5,
                    latent_v=cfg.mla.kv_lora_rank)         # [H, C, r]
                return o.transpose(1, 0, 2)[None]
            x, new_pools, stats = self._latent_walk(
                params, pools, x, positions, phys, off, valid, attend)
            return self._head(params, x[:, chunk_len - 1]), new_pools, stats

        xs, stacks = _layer_xs(params["blocks"])

        def body(carry, layer):
            h_in, pools = carry
            lp, i = layer
            ap = lp["attn"]
            with jax.named_scope("attention"):
                h = B.rms_norm(lp["ln1"], h_in, cfg.norm_eps)
                q = h @ ap["wq"]
                k = h @ ap["wk"]
                v = h @ ap["wv"]
                if "bq" in ap:
                    q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
                q = B._split_heads(q, nq, hd)              # [1, Hq, C, D]
                k = B._split_heads(k, nkv, hd)
                v = B._split_heads(v, nkv, hd)
                if "q_norm" in ap:
                    q = B._head_rmsnorm(q, ap["q_norm"], cfg.norm_eps)
                    k = B._head_rmsnorm(k, ap["k_norm"], cfg.norm_eps)
                q = B.rope(q, positions, cfg.rope_theta)
                k = B.rope(k, positions, cfg.rope_theta)

                with jax.named_scope("kv_write"):
                    pools = KC.append_token(pools, spec, i,
                                            k[0].transpose(1, 0, 2),
                                            v[0].transpose(1, 0, 2), phys,
                                            off)
                o = kops.paged_prefill_attention(
                    q[0], pools["k"], pools["v"], table, q_offset,
                    q_offset + chunk_len, layer=i, scale=scale,
                    k_scales=pools.get("k_scale"),
                    v_scales=pools.get("v_scale"))         # [Hq, C, D]
                h_in = h_in + (o.transpose(1, 0, 2).reshape(1, c, nq * hd)
                               @ ap["wo"]).astype(h_in.dtype)
            with jax.named_scope("mlp"):
                hh = B.rms_norm(lp["ln2"], h_in, cfg.norm_eps)
                if stacks is not None:
                    f, st = B.held_moe(dict(lp["moe"], **stacks), hh, cfg,
                                       i, valid)
                    return (h_in + f, pools), st
                f = B.mlp(lp["ffn"], hh)
            return (h_in + f, pools), None

        (x, pools), stats = jax.lax.scan(body, (x, pools), xs)
        logits = self._head(params, x[:, chunk_len - 1])
        return (logits, pools) + ((stats,) if stacks is not None else ())

    def prefill_chunk(self, params, pools, tokens, table, q_offset,
                      chunk_len) -> Tuple:
        out = self._prefill_chunk(params, pools, tokens, table,
                                  jnp.int32(q_offset), jnp.int32(chunk_len))
        return self._keep_stats(out)

    def _keep_stats(self, out) -> Tuple:
        """(logits, pools), keeping a program's expert load aside."""
        if len(out) == 3:
            self.moe_stats = out[2]
        return out[0], out[1]

    def _head(self, params, h):
        """Final norm and output head of hidden rows [B, d] -> [B, V]."""
        with jax.named_scope("head"):
            h = B.rms_norm(params["ln_f"], h, self.cfg.norm_eps)
            if self.cfg.tie_embeddings:
                return B.unembed(params["embed"], h[:, None])[:, 0]
            return B.linear(params["head"], h).astype(jnp.float32)

    def _latent_walk(self, params, pools, x, positions, phys, off, valid,
                     attend):
        """The layers of a latent-attention model over tokens x [B, S, d]
        at ``positions`` [B, S]: each writes its rows into its layer of
        the pool stack at ``(phys, off)`` [B * S] and attends through
        ``attend(q, pool, layer)`` (q [B, S, H, row] -> latent outputs
        [B, S, H, r]). The leading dense layers walk first, then the
        expert layers, both carrying the one pool stack of every layer
        whole (the expert walk's pool layer is its expert-stack index
        plus the dense layers'). Returns (x, pools, expert load [L_moe,
        3])."""
        cfg = self.cfg
        nd = cfg.moe.first_dense_layers

        def layer(h_in, lp, pool, i):
            with jax.named_scope("attention"):
                h = B.rms_norm(lp["ln1"], h_in, cfg.norm_eps)
                q, row = B.mla_absorbed(lp["attn"], h, positions, cfg)
                with jax.named_scope("kv_write"):
                    pool = KC.append_latent(
                        pool, i, row.reshape(-1, row.shape[-1]), phys, off)
                o = attend(jnp.pad(q, ((0, 0),) * 3 + (
                    (0, pool.shape[-1] - q.shape[-1]),)), pool, i)
                h_in = h_in + B.mla_output(lp["attn"], o, cfg).astype(
                    h_in.dtype)
            with jax.named_scope("mlp"):
                return h_in, B.rms_norm(lp["ln2"], h_in, cfg.norm_eps), pool

        def dense(carry, xs):
            (h_in, pool), (lp, i) = carry, xs
            h_in, hh, pool = layer(h_in, lp, pool, i)
            with jax.named_scope("mlp"):
                f = B.mlp(lp["ffn"], hh)
            return (h_in + f, pool), None

        xs, stacks = _layer_xs(params["blocks"])

        def expert(carry, xs):
            (h_in, pool), (lp, i) = carry, xs
            h_in, hh, pool = layer(h_in, lp, pool, nd + i)
            with jax.named_scope("mlp"):
                f, st = B.held_moe(dict(lp["moe"], **stacks), hh, cfg, i,
                                   valid)
            return (h_in + f, pool), st

        pool = pools["latent"]
        if nd:
            (x, pool), _ = jax.lax.scan(dense, (x, pool),
                                        _layer_xs(params["dense"])[0])
        (x, pool), stats = jax.lax.scan(expert, (x, pool), xs)
        return x, {"latent": pool}, stats

    def _copy_block_impl(self, pools, src, dst):
        """Copy-on-write helper: clone physical block ``src`` into ``dst``
        across every pool tensor (block axis 1 of every page-major
        stack)."""
        return {k: p.at[:, dst].set(p[:, src]) for k, p in pools.items()}

    def copy_block(self, pools, src, dst) -> Dict:
        return self._copy_block(pools, jnp.int32(src), jnp.int32(dst))

    # ---- decode -------------------------------------------------------
    def _decode_impl(self, params, pools, tokens, tables, ctx_lens):
        """One decode step for all slots.

        tokens: [slots] int32 (the pending token per lane); tables:
        [slots, T] int32; ctx_lens: [slots] int32 (KV written so far —
        the pending token's position). Returns (logits [slots, V],
        updated pools). Each layer's phases run under ``jax.named_scope``
        names that device traces keep in the operations' metadata:
        ``attention`` (norm, projections, rope, the paged kernel, the
        output projection), ``attention/kv_write`` (the token's K/V
        into its pool block), ``mlp``; then ``head``. A model with
        experts also returns their load (int32 [L_moe, 3])."""
        cfg, spec = self.cfg, self.spec
        nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        slots = tokens.shape[0]
        scale = hd ** -0.5

        x = B.embed(params["embed"], tokens[:, None])      # [slots, 1, d]
        positions = ctx_lens[:, None].astype(jnp.int32)    # [slots, 1]
        blk = (ctx_lens // spec.block_size)[:, None]
        phys = jnp.take_along_axis(tables, blk, axis=1)[:, 0]   # [slots]
        off = ctx_lens % spec.block_size
        # the kernel's context: the pending token included, none for a
        # dead lane (its table starts at the null block)
        attend = jnp.where(tables[:, 0] != 0, ctx_lens + 1, 0)
        valid = (attend > 0)[:, None]                      # [slots, 1]
        if cfg.latent:
            def latent_attend(q, pool, i):                 # [slots,1,H,row]
                return kops.paged_decode_attention(
                    q[:, 0], pool, None, tables, attend, layer=i,
                    scale=cfg.mla.qk_head_dim ** -0.5,
                    latent_v=cfg.mla.kv_lora_rank)[:, None]
            x, new_pools, stats = self._latent_walk(
                params, pools, x, positions, phys, off, valid,
                latent_attend)
            return self._head(params, x[:, 0]), new_pools, stats

        xs, stacks = _layer_xs(params["blocks"])

        def body(carry, layer):
            h_in, pools = carry
            lp, i = layer
            ap = lp["attn"]
            with jax.named_scope("attention"):
                h = B.rms_norm(lp["ln1"], h_in, cfg.norm_eps)
                q = h @ ap["wq"]
                k = h @ ap["wk"]
                v = h @ ap["wv"]
                if "bq" in ap:
                    q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
                q = B._split_heads(q, nq, hd)              # [slots,Hq,1,D]
                k = B._split_heads(k, nkv, hd)
                v = B._split_heads(v, nkv, hd)
                if "q_norm" in ap:
                    q = B._head_rmsnorm(q, ap["q_norm"], cfg.norm_eps)
                    k = B._head_rmsnorm(k, ap["k_norm"], cfg.norm_eps)
                q = B.rope(q, positions, cfg.rope_theta)
                k = B.rope(k, positions, cfg.rope_theta)

                with jax.named_scope("kv_write"):
                    pools = KC.append_token(pools, spec, i, k[:, :, 0],
                                            v[:, :, 0], phys, off)
                o = kops.paged_decode_attention(
                    q[:, :, 0], pools["k"], pools["v"], tables, attend,
                    layer=i, scale=scale, k_scales=pools.get("k_scale"),
                    v_scales=pools.get("v_scale"))         # [slots,Hq,D]
                h_in = h_in + (o.reshape(slots, 1, nq * hd)
                               @ ap["wo"]).astype(h_in.dtype)
            with jax.named_scope("mlp"):
                hh = B.rms_norm(lp["ln2"], h_in, cfg.norm_eps)
                if stacks is not None:
                    f, st = B.held_moe(dict(lp["moe"], **stacks), hh, cfg,
                                       i, valid)
                    return (h_in + f, pools), st
                f = B.mlp(lp["ffn"], hh)
            return (h_in + f, pools), None

        (x, pools), stats = jax.lax.scan(body, (x, pools), xs)
        with jax.named_scope("head"):
            x = B.rms_norm(params["ln_f"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = B.unembed(params["embed"], x)[:, 0]
            else:
                logits = B.linear(params["head"], x).astype(
                    jnp.float32)[:, 0]
        return (logits, pools) + ((stats,) if stacks is not None else ())

    def decode(self, params, pools, tokens, tables, ctx_lens) -> Tuple:
        return self._keep_stats(
            self._decode(params, pools, tokens, tables, ctx_lens))

    # ---- speculative verify -------------------------------------------
    def _verify_impl(self, params, pools, tokens, tables, ctx_lens,
                     chunk_lens):
        """Score a draft window of C = k+1 tokens per lane in ONE target
        forward (the speculative-decode verify pass).

        tokens: [slots, C] int32 — column 0 is the lane's pending token,
        columns 1..k its greedy draft proposals; tables: [slots, T];
        ctx_lens: [slots] int32 (KV written so far — column c sits at
        absolute position ctx + c); chunk_lens: [slots] int32 per-lane
        window (rows at or past a lane's chunk_len neither append K/V
        nor produce meaningful logits — they are masked to the
        null-block contract, which also covers dead lanes via ctx 0 /
        table 0 / chunk C). Verification is exactly a chunk of decode
        positions attending through the lane's block table, so the walk
        mirrors ``_prefill_chunk_impl`` batched over lanes (the chunked
        Pallas kernel runs per lane inside the jit via
        :func:`repro.kernels.ops.paged_verify_attention`). Returns
        (logits [slots, C, V], updated pools) — row c of a lane is the
        next-token distribution after draft position c, which the
        scheduler compares against the proposals for exact-match
        acceptance."""
        cfg, spec = self.cfg, self.spec
        if cfg.latent:
            raise NotImplementedError(
                "speculative verify of a latent-attention model")
        nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        slots, c = tokens.shape
        scale = hd ** -0.5

        x = B.embed(params["embed"], tokens)               # [slots, C, d]
        cols = jnp.arange(c, dtype=jnp.int32)
        positions = ctx_lens[:, None] + cols[None, :]      # [slots, C]
        valid = cols[None, :] < chunk_lens[:, None]
        safe_pos = jnp.where(valid, positions, 0)
        blk = safe_pos // spec.block_size
        phys = jnp.take_along_axis(tables, blk, axis=1)
        phys = jnp.where(valid, phys, 0).reshape(-1)       # [slots*C]
        off = jnp.where(valid, safe_pos % spec.block_size, 0).reshape(-1)

        xs, stacks = _layer_xs(params["blocks"])

        def body(carry, layer):
            h_in, pools = carry
            lp, i = layer
            ap = lp["attn"]
            h = B.rms_norm(lp["ln1"], h_in, cfg.norm_eps)
            q = h @ ap["wq"]
            k = h @ ap["wk"]
            v = h @ ap["wv"]
            if "bq" in ap:
                q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
            q = B._split_heads(q, nq, hd)                  # [slots,Hq,C,D]
            k = B._split_heads(k, nkv, hd)
            v = B._split_heads(v, nkv, hd)
            if "q_norm" in ap:
                q = B._head_rmsnorm(q, ap["q_norm"], cfg.norm_eps)
                k = B._head_rmsnorm(k, ap["k_norm"], cfg.norm_eps)
            q = B.rope(q, positions, cfg.rope_theta)
            k = B.rope(k, positions, cfg.rope_theta)

            k_rows = k.transpose(0, 2, 1, 3).reshape(slots * c, nkv, hd)
            v_rows = v.transpose(0, 2, 1, 3).reshape(slots * c, nkv, hd)
            pools = KC.append_token(pools, spec, i, k_rows, v_rows, phys,
                                    off)
            o = kops.paged_verify_attention(
                q, pools["k"], pools["v"], tables, ctx_lens, chunk_lens,
                layer=i, scale=scale, k_scales=pools.get("k_scale"),
                v_scales=pools.get("v_scale"))         # [slots, Hq, C, D]
            h_in = h_in + (o.transpose(0, 2, 1, 3).reshape(slots, c,
                                                           nq * hd)
                           @ ap["wo"]).astype(h_in.dtype)
            hh = B.rms_norm(lp["ln2"], h_in, cfg.norm_eps)
            if stacks is not None:
                f, _ = B.held_moe(dict(lp["moe"], **stacks), hh, cfg, i,
                                  valid)
            else:
                f = B.mlp(lp["ffn"], hh)
            return (h_in + f, pools), None

        (x, new_pools), _ = jax.lax.scan(body, (x, pools), xs)
        x = B.rms_norm(params["ln_f"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = B.unembed(params["embed"], x)
        else:
            logits = B.linear(params["head"], x).astype(jnp.float32)
        return logits, new_pools

    def verify(self, params, pools, tokens, tables, ctx_lens,
               chunk_lens) -> Tuple:
        return self._verify(params, pools, tokens, tables, ctx_lens,
                            chunk_lens)

    def restore_rows(self, pools, rows, phys, off) -> Dict:
        """:func:`repro.serve.kvcache.scatter_rows` into the donated
        pools, in place."""
        return self._restore(pools, rows, phys, off)

    # ---- sampling -----------------------------------------------------
    def make_sampler(self, sampling: str = "greedy",
                     temperature: float = 1.0):
        """Jitted sampler(logits [B, V], key) -> tokens [B] int32."""
        if sampling == "greedy":
            @jax.jit
            def sample(logits, key):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        elif sampling == "temperature":
            t = float(temperature)

            @jax.jit
            def sample(logits, key):
                return jax.random.categorical(
                    key, logits / t, axis=-1).astype(jnp.int32)
        else:
            raise ValueError(
                f"unknown sampling {sampling!r} (greedy|temperature)")
        return sample

    def pad_prompt(self, prompt) -> Tuple:
        """Host helper: right-pad a [s] prompt to the fixed prefill
        bucket. Returns (tokens [1, max_context] int32, length int32)."""
        import numpy as np
        s = len(prompt)
        if s > self.max_context:
            raise ValueError(f"prompt length {s} > max_context "
                             f"{self.max_context}")
        buf = np.zeros((1, self.max_context), np.int32)
        buf[0, :s] = np.asarray(prompt, np.int32)
        return jnp.asarray(buf), jnp.int32(s)


class DraftEngine:
    """Speculative-decode draft proposer sharing the target's machinery.

    Wraps the *target* :class:`PagedEngine`'s compiled forwards with the
    distilled student's params (base + merged LoRA factors from
    ``DistillFLStrategy.pod_params`` — shared weights, no second
    checkpoint, no second compile) and a parallel set of pool tensors.
    Block tables and context lengths are the scheduler's own: K/V rows
    are a pure function of the token prefix, so the target's logical
    layout — including prefix-shared blocks, which the scheduler mirrors
    into the draft pools at prefill/copy-on-write time — is valid for
    the draft pools verbatim."""

    def __init__(self, engine: PagedEngine, params, *, draft_k: int):
        if draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        self.engine = engine
        self.spec = engine.spec
        self.params = params
        self.draft_k = int(draft_k)
        self.pools = engine.init_pools()

    def propose(self, tokens, tables, ctx_lens, window):
        """Greedily draft up to ``draft_k`` tokens per lane.

        tokens: [slots] int32 pending tokens; tables: [slots, T];
        ctx_lens: [slots]; window: [slots] per-lane draft budget
        (min(draft_k + 1, tokens the lane may still emit); 0 masks a
        lane out entirely). Runs ``draft_k + 1`` batched draft decode
        forwards — forward i deposits token i's K/V at position ctx + i
        and proposes token i+1 — so even after a full accept the draft
        pools hold the true stream's K/V at every position below the new
        context length. A lane is masked to the dead-lane contract for
        forwards at or past its window, keeping appends inside its
        funded blocks. Returns drafts [slots, draft_k] int32 (zeros past
        a lane's window)."""
        import numpy as np
        slots = len(tokens)
        drafts = np.zeros((slots, self.draft_k), np.int32)
        tok = np.asarray(tokens, np.int32)
        tables = np.asarray(tables, np.int32)
        ctx = np.asarray(ctx_lens, np.int32)
        window = np.asarray(window, np.int32)
        for i in range(self.draft_k + 1):
            live = window > i
            t_i = np.where(live, tok, 0).astype(np.int32)
            tab_i = np.where(live[:, None], tables, 0).astype(np.int32)
            c_i = np.where(live, ctx + i, 0).astype(np.int32)
            logits, self.pools = self.engine.decode(
                self.params, self.pools, jnp.asarray(t_i),
                jnp.asarray(tab_i), jnp.asarray(c_i))
            tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            if i < self.draft_k:
                drafts[:, i] = np.where(window > i + 1, tok, 0)
        return drafts

    # ---- prefill mirroring (scheduler-driven) -------------------------
    def prefill(self, tokens, length) -> None:
        """Monolithic mirror: run the draft model's bucketed prefill and
        keep only its K/V (the stream samples from the target)."""
        _, k, v = self.engine.prefill(self.params, tokens, length)
        self._mirror_kv = (k, v)

    def write_prefill(self, table_row) -> None:
        k, v = self._mirror_kv
        self.pools = self.engine.write_prefill(self.pools, k, v, table_row)
        self._mirror_kv = None

    def prefill_chunk(self, tokens, table, pos, clen) -> None:
        """Chunked mirror: same chunk, draft params, draft pools."""
        _, self.pools = self.engine.prefill_chunk(
            self.params, self.pools, tokens, table, pos, clen)

    def copy_block(self, src, dst) -> None:
        """Copy-on-write mirror for whole-prompt prefix hits."""
        self.pools = self.engine.copy_block(self.pools, src, dst)
