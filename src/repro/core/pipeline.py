"""FHDP intra-cluster pipeline parallelism (paper §4, Fig. 3).

The ``model`` mesh axis hosts the pipeline stages of one vehicle cluster;
``data`` (and ``pod``) hosts FL clients/regions. A GPipe-style microbatch
schedule runs as a single ``lax.scan`` over ticks inside ``shard_map``, with
``jax.lax.ppermute`` moving activations along the stage ring — the
TPU-idiomatic realization of the paper's RPC pipeline (DESIGN.md §2).

Paper-faithful elements:
  * **Every rank feeds data** (the paper's dynamic stage-exchange fix for
    non-i.i.d. utilization in classic HDP): the batch is sharded over *all*
    mesh axes including ``model``; each rank embeds its own samples locally
    and only the *embeddings* are gathered to feed the pipeline head (raw
    sensor inputs never leave their rank — the paper's privacy analogue;
    labels do move to the loss stage, as in any intra-cluster pipeline).
  * **Unequal stage templates** (SWIFT output, Eq. 11): layers are stacked
    to ``[S, Lmax, ...]`` with a per-slot validity mask, so heterogeneous
    partitions lower as one SPMD program.
  * **Stage rotation** (§4 "vehicles systematically rotate through pipeline
    stages"): :func:`rotate_stages` rolls stage ownership around the ring;
    under SPMD the data-utilization benefit is inherent (all ranks always
    contribute samples), so rotation exercises the mechanism the paper needs
    on heterogeneous hardware.

Memory: optimizer state is ZeRO-2 sharded over ``data`` (flattened
reduce-scatter / all-gather update). The paper's Eq. (6) c1 grows clusters
until the memory constraint holds; on a fixed mesh the analogous lever is
sharding optimizer state (and, for MoE, expert weights) over ``data``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ShapeConfig
from repro.models import blocks as B


# --------------------------------------------------------------------------
# Stage templates
# --------------------------------------------------------------------------
def balanced_template(num_layers: int, stages: int) -> Tuple[int, ...]:
    """Even split; first ``num_layers % stages`` stages get one extra."""
    base, rem = divmod(num_layers, stages)
    return tuple(base + (1 if s < rem else 0) for s in range(stages))


def template_offsets(template: Sequence[int]) -> Tuple[int, ...]:
    off, out = 0, []
    for c in template:
        out.append(off)
        off += c
    return tuple(out)


def stack_stages(blocks, template: Sequence[int]):
    """[L, ...] stacked blocks -> ([S, Lmax, ...] padded, mask [S, Lmax]).

    Padded slots repeat layer 0 (their values are masked out), so the
    lowering stays uniform across stages.
    """
    S = len(template)
    lmax = max(max(template), 1)
    offsets = template_offsets(template)
    idx, mask = [], []
    for s in range(S):
        idx.append([offsets[s] + i if i < template[s] else 0
                    for i in range(lmax)])
        mask.append([i < template[s] for i in range(lmax)])
    idx = jnp.asarray(idx)
    mask = jnp.asarray(mask, jnp.bool_)
    return jax.tree.map(lambda x: x[idx], blocks), mask


def rotate_stages(stage_tree, shift: int):
    """Roll stage ownership around the ring (paper's stage rotation)."""
    return jax.tree.map(lambda x: jnp.roll(x, shift, axis=0), stage_tree)


# --------------------------------------------------------------------------
# Family adapters
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FamilyAdapter:
    stack_order: Tuple[str, ...]
    split: Callable      # params -> (shared, {name: [L, ...]})
    counts: Callable     # cfg -> {name: L}
    embed: Callable      # (shared, batch, cfg) -> act dict (incl. 'aux')
    block: Callable      # (stack, layer_params, act, cfg, window, shared) -> act
    loss: Callable       # (shared, act, batch_mb, cfg) -> (loss_sum, n, metrics)


def _ce_sum(shared, x, labels):
    from repro.train.losses import chunked_ce
    w = shared["head"]["w"] if "head" in shared else shared["embed"]["table"].T
    loss, metrics = chunked_ce(x, w, labels, seq_chunk=512)
    n = jnp.asarray(labels.size, jnp.float32)
    return loss * n, n, metrics


def _aux0(x):
    return jnp.zeros((x.shape[0],), jnp.float32)


# ---- decoder LM (dense / moe / vlm) ----
def _lm_split(params):
    return ({k: v for k, v in params.items() if k != "blocks"},
            {"blocks": params["blocks"]})


def _lm_embed(shared, batch, cfg):
    x = B.embed(shared["embed"], batch["tokens"])
    if cfg.prefix_tokens and "patches" in batch:
        pfx = B.linear(shared["projector"], batch["patches"].astype(x.dtype))
        x = jnp.concatenate([pfx, x], axis=1)
    return {"x": x, "aux": _aux0(x)}


def _lm_block(stack, lp, act, cfg, window, shared=None):
    from repro.models.lm import apply_block
    pos = jnp.arange(act["x"].shape[1], dtype=jnp.int32)
    out, _, aux = apply_block(lp, act["x"], cfg, positions=pos, window=window)
    return dict(act, x=out, aux=act["aux"] + aux / act["aux"].shape[0])


def _lm_loss(shared, act, batch, cfg):
    x = act["x"]
    if cfg.prefix_tokens and x.shape[1] > batch["labels"].shape[1]:
        x = x[:, x.shape[1] - batch["labels"].shape[1]:]
    x = B.rms_norm(shared["ln_f"], x, cfg.norm_eps)
    ls, n, metrics = _ce_sum(shared, x, batch["labels"])
    return ls + act["aux"].sum() * n / act["aux"].shape[0], n, metrics


# ---- xLSTM (stage unit = super-block) ----
def _xlstm_split(params):
    return ({k: v for k, v in params.items() if k not in ("mlstm", "slstm")},
            {"mlstm": params["mlstm"], "slstm": params["slstm"]})


def _tok_embed(shared, batch, cfg):
    x = B.embed(shared["embed"], batch["tokens"])
    return {"x": x, "aux": _aux0(x)}


def _xlstm_block(stack, lp, act, cfg, window, shared=None):
    from repro.models import recurrent as R
    x = act["x"]
    if stack == "mlstm":
        def body(h, p):
            y, _ = R.apply_mlstm_seq(p, h, cfg, state=None)
            return h + y, None
        x, _ = lax.scan(body, x, lp)
    else:
        y, _ = R.apply_slstm_seq(lp, x, cfg, state=None)
        x = x + y
    return dict(act, x=x)


def _head_ce_loss(shared, act, batch, cfg):
    x = B.rms_norm(shared["ln_f"], act["x"], cfg.norm_eps)
    return _ce_sum(shared, x, batch["labels"])


# ---- Hymba hybrid ----
def _hymba_block(stack, lp, act, cfg, window, shared=None):
    from repro.models.hymba import apply_block
    pos = jnp.arange(act["x"].shape[1], dtype=jnp.int32)
    out, _, _ = apply_block(lp, act["x"], cfg, positions=pos, window=window)
    return dict(act, x=out)


# ---- encoder-decoder: enc stack then dec stack, memory frozen in-band ----
def _encdec_split(params):
    return ({k: v for k, v in params.items()
             if k not in ("enc_blocks", "dec_blocks")},
            {"enc": params["enc_blocks"], "dec": params["dec_blocks"]})


def _encdec_embed(shared, batch, cfg):
    enc = B.linear(shared["frontend"], batch["frames"].astype(cfg.dtype))
    dec = B.embed(shared["embed"], batch["tokens"])
    return {"enc": enc, "dec": dec, "mem": jnp.zeros_like(enc),
            "enc_done": jnp.zeros((enc.shape[0],), jnp.float32),
            "aux": _aux0(enc)}


def _encdec_block(stack, lp, act, cfg, window, shared=None):
    pos_e = jnp.arange(act["enc"].shape[1], dtype=jnp.int32)
    pos_d = jnp.arange(act["dec"].shape[1], dtype=jnp.int32)
    if stack == "enc":
        h = act["enc"]
        a, _ = B.attention(lp["attn"], B.rms_norm(lp["ln1"], h, cfg.norm_eps),
                           cfg, positions=pos_e, causal=False, window=window)
        h = h + a
        h = h + B.mlp(lp["ffn"], B.rms_norm(lp["ln2"], h, cfg.norm_eps))
        return dict(act, enc=h)
    # decoder block; the first one freezes the (enc_ln'd) encoder memory
    done = act["enc_done"][:, None, None] > 0
    enc_out = B.rms_norm(shared["enc_ln"], act["enc"], cfg.norm_eps) \
        if shared is not None else act["enc"]
    mem = jnp.where(done, act["mem"], enc_out)
    h = act["dec"]
    a, _ = B.attention(lp["attn"], B.rms_norm(lp["ln1"], h, cfg.norm_eps),
                       cfg, positions=pos_d, causal=True, window=window)
    h = h + a
    nkv, hd = cfg.num_kv_heads, cfg.hd
    bsz, sm, _ = mem.shape
    ck = (mem @ lp["xattn"]["wk"]).reshape(bsz, sm, nkv, hd).transpose(0, 2, 1, 3)
    cv = (mem @ lp["xattn"]["wv"]).reshape(bsz, sm, nkv, hd).transpose(0, 2, 1, 3)
    xa, _ = B.attention(lp["xattn"], B.rms_norm(lp["ln_x"], h, cfg.norm_eps),
                        cfg, positions=pos_d, cross_kv=(ck, cv),
                        cross_pos=jnp.arange(sm, dtype=jnp.int32), causal=False)
    h = h + xa
    h = h + B.mlp(lp["ffn"], B.rms_norm(lp["ln2"], h, cfg.norm_eps))
    return dict(act, dec=h, mem=mem,
                enc_done=jnp.ones_like(act["enc_done"]))


def _encdec_loss(shared, act, batch, cfg):
    x = B.rms_norm(shared["ln_f"], act["dec"], cfg.norm_eps)
    return _ce_sum(shared, x, batch["labels"])


# ---- the paper's vision encoder ----
def _vision_embed(shared, batch, cfg):
    rgb = B.linear(shared["rgb_proj"], batch["rgb"].astype(cfg.dtype))
    lid = B.linear(shared["lidar_proj"], batch["lidar"].astype(cfg.dtype))
    x = jnp.concatenate([rgb + shared["modality_emb"][0],
                         lid + shared["modality_emb"][1]], axis=1)
    return {"x": x, "aux": _aux0(x)}


def _vision_block(stack, lp, act, cfg, window, shared=None):
    pos = jnp.arange(act["x"].shape[1], dtype=jnp.int32)
    h = act["x"]
    a, _ = B.attention(lp["attn"], B.rms_norm(lp["ln1"], h, cfg.norm_eps),
                       cfg, positions=pos, causal=False)
    h = h + a
    h = h + B.mlp(lp["ffn"], B.rms_norm(lp["ln2"], h, cfg.norm_eps))
    return dict(act, x=h)


def _vision_loss(shared, act, batch, cfg):
    feats = B.rms_norm(shared["ln_f"], act["x"], cfg.norm_eps)
    b = feats.shape[0]
    q = jnp.broadcast_to(shared["queries"][None],
                         (b,) + shared["queries"].shape)
    nkv, hd = cfg.num_kv_heads, cfg.hd
    k = (feats @ shared["dec_attn"]["wk"]).reshape(
        b, -1, nkv, hd).transpose(0, 2, 1, 3)
    v = (feats @ shared["dec_attn"]["wv"]).reshape(
        b, -1, nkv, hd).transpose(0, 2, 1, 3)
    qpos = jnp.arange(q.shape[1], dtype=jnp.int32)
    dec, _ = B.attention(shared["dec_attn"],
                         B.rms_norm(shared["dec_ln"], q, cfg.norm_eps), cfg,
                         positions=qpos, cross_kv=(k, v),
                         cross_pos=jnp.arange(feats.shape[1], dtype=jnp.int32),
                         causal=False)
    dec = dec + q
    wp = B.linear(shared["wp_head"],
                  dec[:, :cfg.num_waypoints]).astype(jnp.float32)
    light = B.linear(shared["light_head"], dec[:, -1]).astype(jnp.float32)
    l1 = jnp.abs(wp - batch["waypoints"]).mean()
    logp = jax.nn.log_softmax(light)
    ce = -jnp.take_along_axis(logp, batch["light"][:, None], axis=-1).mean()
    n = jnp.asarray(b, jnp.float32)
    return (l1 + ce) * n, n, {"l1": l1, "ce": ce}


def get_adapter(cfg: ModelConfig) -> FamilyAdapter:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return FamilyAdapter(("blocks",), _lm_split,
                             lambda c: {"blocks": c.num_layers},
                             _lm_embed, _lm_block, _lm_loss)
    if fam == "ssm":
        from repro.models.xlstm import _layout

        def counts(c):
            n_super, _ = _layout(c)
            return {"mlstm": n_super, "slstm": n_super}

        return FamilyAdapter(("mlstm", "slstm"), _xlstm_split, counts,
                             _tok_embed, _xlstm_block, _head_ce_loss)
    if fam == "hybrid":
        return FamilyAdapter(("blocks",), _lm_split,
                             lambda c: {"blocks": c.num_layers},
                             _tok_embed, _hymba_block, _head_ce_loss)
    if fam == "encdec":
        return FamilyAdapter(("enc", "dec"), _encdec_split,
                             lambda c: {"enc": c.enc_layers,
                                        "dec": c.dec_layers},
                             _encdec_embed, _encdec_block, _encdec_loss)
    if fam == "vision":
        return FamilyAdapter(("blocks",), _lm_split,
                             lambda c: {"blocks": c.num_layers},
                             _vision_embed, _vision_block, _vision_loss)
    raise ValueError(fam)


# --------------------------------------------------------------------------
# Stage-stacked parameter container
# --------------------------------------------------------------------------
def template_from_sequence(cfg: ModelConfig, seq: Sequence[int]
                           ) -> Dict[str, Tuple[int, ...]]:
    """Split a flat per-stage layer-count template over the model's stacks.

    ``seq[s]`` counts layers of the concatenated stack sequence (the
    adapter's ``stack_order`` concatenation) assigned to stage ``s`` — the
    form SWIFT's :func:`repro.sched.swift.units_to_layer_template` emits.
    Raises if the sequence does not cover the model exactly (a template
    that drops or invents layers must never reach the runtime).
    """
    adapter = get_adapter(cfg)
    counts = adapter.counts(cfg)
    total = sum(counts.values())
    seq = tuple(int(c) for c in seq)
    if sum(seq) != total:
        raise ValueError(
            f"stage template {seq} covers {sum(seq)} layers but the model "
            f"has {total} ({counts}); refusing to drop/invent layers")
    offs = template_offsets(seq)
    out, start = {}, 0
    for name in adapter.stack_order:
        L = counts[name]
        out[name] = tuple(
            max(0, min(offs[s] + seq[s], start + L) - max(offs[s], start))
            for s in range(len(seq)))
        start += L
    return out


def make_templates(cfg: ModelConfig, stages: int,
                   template: Optional[Dict[str, Sequence[int]]] = None
                   ) -> Dict[str, Tuple[int, ...]]:
    """Per-stack stage templates. The default splits the concatenated layer
    sequence (stack_order concatenation) evenly across stages. Custom
    templates come from SWIFT (sched/swift.py)."""
    if template is not None:
        return {k: tuple(v) for k, v in template.items()}
    adapter = get_adapter(cfg)
    total = sum(adapter.counts(cfg).values())
    return template_from_sequence(cfg, balanced_template(total, stages))


def _abstract_params_thunk(cfg: ModelConfig):
    from repro.models import build_model
    model = build_model(cfg)
    return lambda: model.init(jax.random.PRNGKey(0))


def stage_params_from(params, cfg: ModelConfig,
                      templates: Dict[str, Sequence[int]]):
    """Full params -> {'shared', 'stacks': {name: [S, Lmax, ...]},
    'masks': {name: [S, Lmax]}} container."""
    adapter = get_adapter(cfg)
    shared, stacks = adapter.split(params)
    out_stacks, masks = {}, {}
    for name, blocks in stacks.items():
        st, mask = stack_stages(blocks, templates[name])
        out_stacks[name] = st
        masks[name] = mask
    return {"shared": shared, "stacks": out_stacks, "masks": masks}


_STACK_TO_PARAM = {"blocks": "blocks", "enc": "enc_blocks",
                   "dec": "dec_blocks", "mlstm": "mlstm", "slstm": "slstm"}


def merge_stage_params(pp, templates: Dict[str, Sequence[int]]):
    """Inverse of :func:`stage_params_from` (used by recovery/backup)."""
    merged = dict(pp["shared"])
    for name, st in pp["stacks"].items():
        tmpl = templates[name]

        def unstack(x):
            parts = [x[s, :tmpl[s]] for s in range(len(tmpl)) if tmpl[s]]
            return jnp.concatenate(parts, axis=0)

        merged[_STACK_TO_PARAM.get(name, name)] = jax.tree.map(unstack, st)
    return merged


def stage_specs(mesh: Mesh, pp_shape) -> Any:
    """Stacks and masks sharded over ``model`` on the stage dim; shared
    params replicated."""
    def spec(path, leaf):
        keys = [e.key for e in path if isinstance(e, jax.tree_util.DictKey)]
        if keys and keys[0] in ("stacks", "masks"):
            return P("model", *([None] * (len(leaf.shape) - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, pp_shape)


# --------------------------------------------------------------------------
# ZeRO-2 optimizer state (flattened, data-sharded Adam moments)
# --------------------------------------------------------------------------
def _flat_shard(n: int, d: int) -> int:
    return (n + d - 1) // d


def zero2_init(pp, data_size: int, sharded: bool = True):
    """Adam moments, flattened per LOCAL leaf.

    Stage stacks keep their leading stage dim (sharded over ``model``);
    within a stage the flat moments are split over ``data`` when
    ``sharded=True`` (ZeRO-2 — valid when gradients are synchronized every
    step) or kept whole per column for FedAvg local steps (columns
    diverge, so moments cannot be sharded across them). Global layouts:
      stacks : [S, D, n]  with P('model', 'data')
      shared : [D, n]     with P('data')
    """
    def shard(path, x):
        keys = [e.key for e in path if isinstance(e, jax.tree_util.DictKey)]
        staged = bool(keys) and keys[0] == "stacks"
        if not jnp.issubdtype(x.dtype, jnp.inexact):
            return jnp.zeros(((x.shape[0], data_size, 0) if staged
                              else (data_size, 0)), jnp.float32)
        if staged:
            n_loc = x.size // x.shape[0]
            n = _flat_shard(n_loc, data_size) if sharded else n_loc
            return jnp.zeros((x.shape[0], data_size, n), jnp.float32)
        n = _flat_shard(x.size, data_size) if sharded else x.size
        return jnp.zeros((data_size, n), jnp.float32)

    return {"step": jnp.zeros((), jnp.int32),
            "m": jax.tree_util.tree_map_with_path(shard, pp),
            "v": jax.tree_util.tree_map_with_path(shard, pp)}


def zero2_specs(opt_shape):
    def spec(leaf):
        if leaf.shape == ():
            return P()
        if len(leaf.shape) == 3:
            return P("model", "data", None)
        return P("data", None)

    return jax.tree.map(spec, opt_shape)


# --------------------------------------------------------------------------
# The pipelined train step
# --------------------------------------------------------------------------
def make_fhdp_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                         microbatches: Optional[int] = None,
                         templates: Optional[Dict[str, Sequence[int]]] = None,
                         learning_rate: float = 3e-4,
                         remat: bool = True,
                         window: Optional[int] = None,
                         fed_sgd: bool = True):
    """Build the FHDP pipelined train step.

    Returns ``(step, helpers)``; ``step(pp, opt, batch) -> (pp, opt,
    metrics)`` over the stage-param container (:func:`stage_params_from`)
    laid out per :func:`stage_specs`.

    ``fed_sgd=True`` synchronizes gradients across FL clients every step
    (equivalent to FL with one local step); ``fed_sgd=False`` runs local
    steps with NO cross-client sync — parameters diverge per data column
    and are averaged by :func:`fedavg_stage_params` at round boundaries
    (true FedAvg, paper §3.1).
    """
    adapter = get_adapter(cfg)
    S = mesh.shape["model"]
    D = mesh.shape["data"]
    pods = mesh.shape.get("pod", 1)
    Bg = shape.global_batch
    B_col = Bg // (D * pods)             # per-pipeline-column batch
    assert Bg % (D * pods) == 0, (Bg, D, pods)
    # microbatch geometry: one microbatch per rank when the column batch
    # allows; columns smaller than the stage count run a partial stream.
    if microbatches:
        M = microbatches
        assert M <= S or M % S == 0, (M, S)
        assert B_col % M == 0, (B_col, M)
        mb = B_col // M
    else:
        mb = max(1, B_col // S)
        M = B_col // mb
    share = (max(M // S, 1)) * mb        # samples each rank embeds
    templates = templates or make_templates(cfg, S)
    lr = learning_rate
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    all_axes = batch_axes + ("model",)
    label_keys = ("labels", "waypoints", "light")

    def device_fn(pp, opt, batch):
        r = lax.axis_index("model")

        def local_loss(pp):
            shared = pp["shared"]
            stacks = jax.tree.map(lambda x: x[0], pp["stacks"])
            masks = {k: v[0] for k, v in pp["masks"].items()}

            def mb_slice(tree, m, size=None):
                sz = size or mb
                return jax.tree.map(
                    lambda x: lax.dynamic_slice_in_dim(x, m, sz, 0), tree)

            # every rank embeds its own share of the column batch; only the
            # resulting features are gathered to feed the pipeline head.
            start = jnp.minimum(r * share, B_col - share)
            my = mb_slice(batch, start, share)
            act0 = adapter.embed(shared, my, cfg)
            gath = lambda x: lax.all_gather(x, "model", axis=0, tiled=True)
            act_all = jax.tree.map(gath, act0)  # rows m*mb..: microbatch m
            lbl_all = {k: v for k, v in batch.items() if k in label_keys}

            def apply_stage(act):
                for name in adapter.stack_order:
                    stack, mask = stacks[name], masks[name]

                    def body(a, xs):
                        lp, valid = xs
                        out = adapter.block(name, lp, a, cfg, window, shared)
                        a2 = jax.tree.map(
                            lambda o, i: jnp.where(valid, o, i), out, a)
                        return a2, None

                    if remat:  # per-layer remat inside the stage
                        body = jax.checkpoint(
                            body,
                            policy=jax.checkpoint_policies.nothing_saveable)
                    act, _ = lax.scan(body, act, (stack, mask))
                return act

            zero_act = jax.tree.map(
                lambda x: jnp.zeros((mb,) + x.shape[1:], x.dtype), act0)
            perm = [(i, (i + 1) % S) for i in range(S)]
            T = M + S - 1

            def tick(carry, t):
                act_in = carry
                head_in = mb_slice(act_all, jnp.clip(t, 0, M - 1) * mb)
                inp = jax.tree.map(lambda h, a: jnp.where(r == 0, h, a),
                                   head_in, act_in)
                out = apply_stage(inp)
                nxt = jax.tree.map(lambda x: lax.ppermute(x, "model", perm),
                                   out)
                # emit the (masked) final-stage activation of microbatch t-r
                fin = jax.tree.map(
                    lambda x: jnp.where(r == S - 1, x, jnp.zeros_like(x)),
                    out)
                return nxt, fin

            tick_fn = jax.checkpoint(tick) if remat else tick
            _, fins = lax.scan(tick_fn, zero_act, jnp.arange(T))

            # The loss was previously computed inside every tick on every
            # rank (S*T redundant head+CE evaluations — measured 4x whole-
            # step FLOP inflation at 16 stages). Instead: broadcast the
            # final-stage microbatch activations once (masked psum) and let
            # every rank evaluate the loss for its own 1/S of microbatches.
            fins = jax.tree.map(lambda x: x[S - 1:], fins)   # ticks -> mb
            fins = jax.tree.map(lambda x: lax.psum(x, "model"), fins)
            per = max(M // S, 1)
            lo = jnp.minimum(r * per, M - per)

            def mb_loss(c, i):
                loss, cnt = c
                m_idx = lo + i
                act_m = jax.tree.map(
                    lambda x: lax.dynamic_slice_in_dim(x, m_idx, 1, 0)[0],
                    fins)
                lsum, n, _ = adapter.loss(shared, act_m,
                                          mb_slice(lbl_all, m_idx * mb), cfg)
                # ranks whose slot is clamped (M < S) recompute a duplicate
                # microbatch — mask them out of the psum
                keep = (r * per + i < M).astype(jnp.float32)
                return (loss + lsum * keep, cnt + n * keep), None

            (loss, cnt), _ = lax.scan(
                mb_loss, (jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.float32)), jnp.arange(per))

            loss = lax.psum(loss, "model") / jnp.maximum(
                lax.psum(cnt, "model"), 1.0)
            if fed_sgd and batch_axes:
                loss = lax.pmean(loss, batch_axes)
            return loss

        loss, grads = jax.value_and_grad(local_loss, allow_int=True)(pp)

        def sync(path, g):
            if not jnp.issubdtype(g.dtype, jnp.inexact):
                return g
            keys = [e.key for e in path
                    if isinstance(e, jax.tree_util.DictKey)]
            if keys and keys[0] == "shared":
                return lax.psum(g, all_axes if (fed_sgd and batch_axes)
                                else ("model",))
            if keys and keys[0] == "stacks" and fed_sgd and batch_axes:
                return lax.psum(g, batch_axes)
            return g

        grads = jax.tree_util.tree_map_with_path(sync, grads)

        # ZeRO-2 Adam on flattened shards
        step = opt["step"] + 1
        b1, b2, eps = 0.9, 0.95, 1e-8
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        zero2 = fed_sgd and D > 1

        def upd(p, g, m, v):
            # all sizes are LOCAL: p/g are this rank's stage view, m/v the
            # flat (possibly data-sharded) moment shards
            n = p.size
            mf, vf = m.reshape(-1), v.reshape(-1)
            shard = mf.size
            if zero2:
                # reduce-scatter grads IN THE GRAD DTYPE (padding the
                # embedding to full float32 costs GiB-scale temps), then
                # update the local shard and all-gather in param dtype
                gf = jnp.pad(g.reshape(-1), (0, shard * D - n))
                gl = lax.psum_scatter(gf.reshape(D, shard), "data",
                                      scatter_dimension=0, tiled=False
                                      ).astype(jnp.float32)
            else:
                gl = g.astype(jnp.float32).reshape(-1)
            m2 = b1 * mf + (1 - b1) * gl
            v2 = b2 * vf + (1 - b2) * gl * gl
            u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            if zero2:
                pf = jnp.pad(p.reshape(-1), (0, shard * D - n))
                pl = lax.dynamic_slice_in_dim(
                    pf, lax.axis_index("data") * shard, shard
                ).astype(jnp.float32) - lr * u
                pg = lax.all_gather(pl.astype(p.dtype), "data", axis=0,
                                    tiled=True)[:n].astype(jnp.float32)
            else:
                pg = p.astype(jnp.float32).reshape(-1) - lr * u
            return (pg.reshape(p.shape).astype(p.dtype),
                    m2.reshape(m.shape), v2.reshape(v.shape))

        flat_p, tdef = jax.tree_util.tree_flatten(pp)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(opt["m"])
        flat_v = tdef.flatten_up_to(opt["v"])
        new_p, new_m, new_v = [], [], []
        for p_, g_, m_, v_ in zip(flat_p, flat_g, flat_m, flat_v):
            if not jnp.issubdtype(p_.dtype, jnp.inexact):
                new_p.append(p_); new_m.append(m_); new_v.append(v_)
                continue
            p2, m2, v2 = upd(p_, g_, m_, v_)
            new_p.append(p2); new_m.append(m2); new_v.append(v2)
        pp2 = jax.tree_util.tree_unflatten(tdef, new_p)
        opt2 = {"step": step,
                "m": jax.tree_util.tree_unflatten(tdef, new_m),
                "v": jax.tree_util.tree_unflatten(tdef, new_v)}
        return pp2, opt2, {"loss": loss}

    # ---- shard_map wiring ----
    pp_abs = jax.eval_shape(
        lambda: stage_params_from(_abstract_params_thunk(cfg)(), cfg,
                                  templates))
    pspec = stage_specs(mesh, pp_abs)
    opt_abs = jax.eval_shape(
        functools.partial(zero2_init, data_size=D,
                          sharded=fed_sgd and D > 1), pp_abs)
    ospec = zero2_specs(opt_abs)
    from repro.configs.common import input_specs
    batch_abs = input_specs(cfg, shape)
    bspec = jax.tree.map(
        lambda x: P(batch_axes, *([None] * (len(x.shape) - 1))), batch_abs)

    step = jax.shard_map(device_fn, mesh=mesh,
                         in_specs=(pspec, ospec, bspec),
                         out_specs=(pspec, ospec, P()),
                         check_vma=False)

    helpers = {"templates": templates, "pp_abs": pp_abs, "opt_abs": opt_abs,
               "pspec": pspec, "ospec": ospec, "bspec": bspec,
               "microbatches": M, "mb": mb, "batch_abs": batch_abs}
    return step, helpers


def fedavg_stage_params(pp, mesh: Mesh):
    """Round-boundary FedAvg for ``fed_sgd=False`` training: average the
    (diverged) per-column parameters over the FL axes — edge aggregation
    over ``data`` then cloud aggregation over ``pod`` (paper Fig. 1)."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def avg(pp):
        return jax.tree.map(
            lambda x: lax.pmean(x, batch_axes)
            if jnp.issubdtype(x.dtype, jnp.inexact) else x, pp)

    spec = stage_specs(mesh, jax.eval_shape(lambda: pp))
    return jax.shard_map(avg, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(pp)
