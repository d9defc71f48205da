"""Latent attention (MLA) and the held-expert MoE layer on the serving
path, against the plain reference of ``bench/configs/moonlight-16b-a3b.py``
at a small size (CPU, seeded random weights, float32)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.loader import BENCH, load_module
from repro.config import MLAConfig, ModelConfig, MoEConfig
from repro.models import blocks as B
from repro.serve import (ContinuousScheduler, PagedCacheSpec, PagedEngine,
                         ServeRequest)
from repro.serve import kvcache as KC

REF = load_module(BENCH / "configs" / "moonlight-16b-a3b.py")

#: Moonlight's keys at a small size: 1 dense + 2 expert layers, 8 routed
#: experts of which this chip holds 4 (experts 2-5), top-3, one shared
TINY = {
    "name": "moonlight-tiny", "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "router_experts": 8, "n_routed_experts": 4,
    "expert_offset": 2, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "rms_norm_eps": 1e-5, "kv_norm_eps": 1e-6,
    "rope_theta": 50000, "rope_interleave": True, "vocab_size": 97,
    "tie_word_embeddings": False, "dtype": "float32",
}
PROGRAM = load_module(BENCH / "configs" / "moonlight-16b-a3b.program.py")


def tiny(**kw):
    c = dict(TINY, **kw)
    return c, PROGRAM.program_config(c)


@pytest.fixture(scope="module")
def model():
    c, cfg = tiny()
    params = jax.jit(lambda k: REF.init_params(c, k))(jax.random.PRNGKey(3))
    return c, cfg, params


def _serve_logits(cfg, params, prompt, n_decode, chunk=4, bs=4, slots=3):
    """Chunked paged prefill of ``prompt`` through the latent pool, then
    ``n_decode`` teacher-forced decode steps (lane 1 of ``slots``; the
    other lanes dead). Returns the logits of the last prompt position and
    of each decoded position, [1 + n_decode, V]."""
    total = len(prompt) + n_decode
    spec = PagedCacheSpec(num_blocks=1 + slots * (-(-total // bs)) + 1,
                          block_size=bs, max_blocks_per_req=-(-total // bs))
    eng = PagedEngine(cfg, spec, max_context=spec.max_tokens_per_req,
                      slots=slots)
    pools = eng.init_pools()
    table = np.zeros(spec.max_blocks_per_req, np.int32)
    table[:] = 1 + np.arange(spec.max_blocks_per_req)
    seq = np.asarray(prompt, np.int32)
    out = []
    for pos in range(0, len(seq), chunk):
        clen = min(chunk, len(seq) - pos)
        buf = np.zeros(chunk, np.int32)
        buf[:clen] = seq[pos:pos + clen]
        logits, pools = eng.prefill_chunk(params, pools, jnp.asarray(buf),
                                          jnp.asarray(table), pos, clen)
    out.append(logits[0])
    follow = np.random.default_rng(5).integers(1, cfg.vocab_size, n_decode)
    tables = np.zeros((slots, spec.max_blocks_per_req), np.int32)
    tables[1] = table
    for i, tok in enumerate(follow):
        toks = np.zeros(slots, np.int32)
        toks[1] = tok
        ctx = np.zeros(slots, np.int32)
        ctx[1] = len(seq) + i
        logits, pools = eng.decode(params, pools, jnp.asarray(toks),
                                   jnp.asarray(tables), jnp.asarray(ctx))
        out.append(logits[1])
    return jnp.stack(out), np.concatenate([seq, follow]), eng


def test_paged_prefill_and_decode_match_the_reference(model):
    """Chunked prefill then decode through the latent pool give the
    reference's full-forward logits. Both run in float32 (matmuls at
    ``highest``); the program takes the absorbed path and a different
    summation order, so they agree to float32 rounding: 1e-4 of logits
    of order 1. Serving in bf16 moves them by about 1e-2."""
    c, cfg, params = model
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, 11)
    got, seq, _ = _serve_logits(cfg, params, prompt, n_decode=5)
    rows = len(prompt) - 1 + np.arange(6)
    want = REF.logits_at(params, c, seq, rows)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_bf16_serving_would_break_the_tolerance(model):
    """The tolerance above is tight enough to tell the stated float32
    from bf16: the same weights served in bf16 miss it."""
    c, cfg, params = model
    cfg16 = cfg.replace(param_dtype="bfloat16")
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1
                       and a.shape[-1] != cfg.moe.num_experts else a, params)
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, 11)
    got, seq, _ = _serve_logits(cfg16, p16, prompt, n_decode=2)
    want = REF.logits_at(params, c, seq, len(prompt) - 1 + np.arange(3))
    assert float(jnp.abs(got - want).max()) > 1e-4


def _published_mla(p, h, cfg):
    """MLA in the published, non-absorbed form over one sequence h [S, d]
    (per-head keys and values up-projected from the latent)."""
    a, nh = cfg.mla, cfg.num_heads
    s = h.shape[0]
    nope, r = a.qk_nope_head_dim, a.kv_lora_rank
    q = (h @ p["wq"]).reshape(s, nh, -1)
    kva = h @ p["wkva"]
    c = B.rms_norm(p["kv_norm"], kva[:, :r], a.kv_norm_eps)
    kv = (c @ p["wkvb"]).reshape(s, nh, -1)
    pos = jnp.arange(s)
    q_pe = B.rope(B._deinterleave(q[None, :, :, nope:]).transpose(0, 2, 1, 3),
                  pos, cfg.rope_theta)[0].transpose(1, 0, 2)
    k_pe = B.rope(B._deinterleave(kva[None, None, :, r:]), pos,
                  cfg.rope_theta)[0, 0]
    sc = (jnp.einsum("qhn,phn->hqp", q[..., :nope], kv[..., :nope])
          + jnp.einsum("qhr,pr->hqp", q_pe, k_pe)) * a.qk_head_dim ** -0.5
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o = jnp.einsum("hqp,phv->qhv", jax.nn.softmax(sc, -1), kv[..., nope:])
    return o.reshape(s, -1) @ p["wo"]


def test_absorbed_mla_equals_the_published_form(model):
    """Taking each head's key up-projection into its query and its value
    up-projection after the latent output is an exact rewrite: the two
    agree to float32 rounding (2e-5 of outputs of order 1)."""
    _, cfg, params = model
    p = jax.tree.map(lambda a: a[0], params["blocks"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(0), (9, cfg.d_model))
    q, row = B.mla_absorbed(p, h[None], jnp.arange(9)[None], cfg)
    sc = jnp.einsum("qhc,pc->hqp", q[0], row[0]) * cfg.mla.qk_head_dim ** -0.5
    pos = jnp.arange(9)
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o_lat = jnp.einsum("hqp,pr->qhr", jax.nn.softmax(sc, -1),
                       row[0, :, :cfg.mla.kv_lora_rank])
    got = B.mla_output(p, o_lat[None], cfg)[0]
    want = _published_mla(p, h, cfg)
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def _moe_params(cfg, key):
    p = B.init_held_moe(key, cfg.replace(moe=dataclasses.replace(
        cfg.moe, experts_held=0, expert_offset=0)))
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(7),
                                        (cfg.moe.num_experts,))
    return p


def _share(p, cfg, offset, held):
    """This chip's slice of an uncut layer's parameters, its experts as
    the stacks of one layer ``[1, held, ...]``, and config."""
    q = dict(p, **{k: p[k][None, offset:offset + held]
                   for k in B.EXPERT_WEIGHTS})
    return q, cfg.replace(moe=dataclasses.replace(cfg.moe, expert_offset=offset,
                                           experts_held=held))


def _dense_moe(p, x, cfg):
    """The uncut layer token by token: every chosen expert applied."""
    idx, w = B.route(p, x, cfg)
    out = B.mlp(p["shared"], x)
    for t in range(x.shape[0]):
        for j in range(cfg.moe.top_k):
            pe = {k: p[k][idx[t, j]] for k in ("wi", "wg", "wo")}
            out = out.at[t].add(w[t, j] * B.mlp(pe, x[t]))
    return out


def test_four_shares_sum_to_the_uncut_layer(model):
    """Expert parallelism over 4 chips: each share computes its experts'
    part plus the shared expert; the four parts, with the shared expert
    counted once, are the uncut layer (float32 rounding, 1e-5)."""
    _, cfg, _ = model
    p = _moe_params(cfg, jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, cfg.d_model))
    shared = B.mlp(p["shared"], x)
    held = cfg.moe.num_experts // 4
    total = -3 * shared
    loads = 0
    for chip in range(4):
        q, c = _share(p, cfg, chip * held, held)
        y, st = B.held_moe(q, x, c, 0)
        total = total + y
        loads += int(st[0])
    want = _dense_moe(p, x.reshape(-1, cfg.d_model), cfg).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=0)
    assert loads == x.shape[0] * x.shape[1] * cfg.moe.top_k


def test_no_token_is_dropped_when_all_pick_the_same_experts(model):
    """A router that sends every token to the same top-k experts: each
    held expert gets all the tokens and computes every one of them."""
    _, cfg, _ = model
    p = _moe_params(cfg, jax.random.PRNGKey(12))
    k = cfg.moe.top_k
    p["bias"] = jnp.where(jnp.arange(cfg.moe.num_experts) < k, 10.0, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, cfg.d_model))
    q, c = _share(p, cfg, 0, cfg.moe.num_experts // 2)
    y, st = B.held_moe(q, x, c, 0)
    assert [int(v) for v in st] == [40 * k, k, 40]
    want = _dense_moe(p, x[0], cfg)
    np.testing.assert_allclose(y[0], want, atol=1e-5, rtol=0)


def test_selection_bias_changes_a_selection(model):
    """The bias moves which experts are chosen, not their weights: with
    it some token's chosen set differs, and the weights of a chosen
    expert are its unbiased score (renormalized, scaled)."""
    _, cfg, _ = model
    p = _moe_params(cfg, jax.random.PRNGKey(13))
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
    idx, w = B.route(p, x, cfg)
    idx0, _ = B.route(dict(p, bias=jnp.zeros_like(p["bias"])), x, cfg)
    assert bool((jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any())
    s = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * cfg.moe.route_scale,
        rtol=1e-6)


def test_dead_lanes_route_nothing(model):
    """Padding rows and dead lanes get no expert: the load counts only
    the valid tokens, and their outputs are the shared expert alone."""
    _, cfg, _ = model
    p = _moe_params(cfg, jax.random.PRNGKey(14))
    q, c = _share(p, cfg, 0, cfg.moe.num_experts)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 1, cfg.d_model))
    valid = jnp.array([[True], [False], [True], [False]])
    y, st = B.held_moe(q, x, c, 0, valid)
    assert int(st[0]) == 2 * cfg.moe.top_k
    np.testing.assert_allclose(y[1], B.mlp(p["shared"], x[1]), atol=1e-6)


def test_scheduler_serves_latent_model_and_counts_expert_load(model):
    """The model runs through ``ContinuousScheduler`` (chunked prefill,
    prefix cache on): greedy streams follow the reference's argmax, and
    the registry counts the decode steps' expert load."""
    c, cfg, params = model
    spec = PagedCacheSpec.for_requests(3, 24, block_size=4, headroom=4)
    eng = PagedEngine(cfg, spec, max_context=24, slots=3)
    sched = ContinuousScheduler(eng, params, prefill="chunked",
                                prefill_chunk=4, prefix_cache=True)
    rng = np.random.default_rng(8)
    reqs = [ServeRequest(rid=i, prompt=rng.integers(1, cfg.vocab_size, n),
                         max_new_tokens=m)
            for i, (n, m) in enumerate([(9, 5), (6, 4), (13, 3)])]
    done = sched.run_to_completion(reqs)
    assert len(done) == 3
    for r in done:
        seq = np.concatenate([r.prompt, r.tokens[:-1]])
        want = REF.logits_at(params, c, seq,
                             len(r.prompt) - 1 + np.arange(len(r.tokens)))
        assert list(np.asarray(jnp.argmax(want, -1))) == list(r.tokens)
    n = sched.metrics.get("serve_moe_held_assignments").value()
    hit = sched.metrics.get("serve_moe_experts_hit").stats()
    busiest = sched.metrics.get("serve_moe_busiest_expert").stats()
    steps = sched.decode_steps_run
    assert hit["count"] == busiest["count"] == steps * 2   # 2 expert layers
    decoded = sum(len(r.tokens) - 1 for r in done)
    assert 0 < n <= decoded * 2 * cfg.moe.top_k
    assert 0 < hit["mean"] <= cfg.moe.held


def test_latent_pool_geometry_and_refusals(model):
    _, cfg, _ = model
    spec = PagedCacheSpec(num_blocks=6, block_size=4, max_blocks_per_req=2)
    pools = KC.init_pools(cfg, spec)
    # one stack of every layer, the dense one first; 40 lanes padded
    assert set(pools) == {"latent"}
    assert pools["latent"].shape == (3, 6, 4, 128)
    eng = PagedEngine(cfg, spec, max_context=8, slots=2)
    keys = set(pools)
    moved = eng.copy_block(pools, 0, 3)     # donates ``pools``
    assert set(moved) == keys
    with pytest.raises(NotImplementedError, match="int8 latent"):
        KC.init_pools(cfg, PagedCacheSpec(6, 4, 2, quantized=True))
    with pytest.raises(NotImplementedError, match="chunked"):
        eng.prefill(None, jnp.zeros((1, 8), jnp.int32), jnp.int32(3))


def test_registry_config_is_the_published_file():
    """``get_config('moonlight-16b-a3b')`` is the benchmark's file at its
    published depth (27 layers) with every expert held (64 of 64)."""
    from bench.harness.loader import read_json
    from repro.configs import get_config

    c = read_json(BENCH / "configs" / "moonlight-16b-a3b.json")
    c.update(num_hidden_layers=27, n_routed_experts=64)
    want = PROGRAM.program_config(c)
    want = want.replace(moe=dataclasses.replace(want.moe, experts_held=0))
    assert get_config("moonlight-16b-a3b") == want


def test_program_init_builds_the_reference_tree(model):
    """``lm.init`` of a latent config (the program's own entry, used by
    ``repro.serve`` when no weights are given) builds the parameter tree
    the reference and the benchmark hand the engine: same paths, shapes
    and dtypes."""
    from repro.models import lm

    c, cfg, params = model
    mine = lm.init(jax.random.PRNGKey(0), cfg)

    def shapes(tree):
        return jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert shapes(mine) == shapes(params)
