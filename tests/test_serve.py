"""Serving tier: paged KV-cache, continuous batching, int8 cache, chunked
prefill, pod prefix sharing, and the incremental-decode consistency
contract behind them all."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ShapeConfig
from repro.configs import get_config
from repro.configs.common import reduced
from repro.serve import (BlockAllocator, ContinuousScheduler,
                         PagedCacheSpec, PagedEngine, PrefillCostModel,
                         PrefixCache, ServeRequest, drive,
                         generate_fleet_requests, generate_pod_requests,
                         int8_cache_fidelity, serve_continuous)
from repro.serve import kvcache as KC
from tests._hyp import given, settings, st

KEY = jax.random.PRNGKey(0)


def _smoke_cfg(arch="flad_adllm"):
    return reduced(get_config(arch)).replace(param_dtype="float32")


@pytest.fixture(scope="module")
def dense_setup():
    from repro.models import lm
    cfg = _smoke_cfg()
    params = lm.init(KEY, cfg)
    return cfg, params


# ------------------------------------------------------ block allocator ----
def test_block_allocator_semantics():
    spec = PagedCacheSpec(num_blocks=8, block_size=4, max_blocks_per_req=3)
    alloc = BlockAllocator(spec)
    assert alloc.free_blocks == 7          # block 0 never enters the pool
    a = alloc.alloc(3)
    assert a is not None and 0 not in a
    assert alloc.alloc(4) is None          # > max_blocks_per_req
    b = alloc.alloc(3)
    assert alloc.free_blocks == 1
    assert alloc.alloc(2) is None          # all-or-nothing: 1 < 2
    assert alloc.free_blocks == 1          # failed alloc strands nothing
    alloc.release(b)
    assert alloc.free_blocks == 4
    assert alloc.alloc(3) is not None      # released blocks recycle
    with pytest.raises(ValueError):
        alloc.release(a + [a[0]])          # double free in one batch
    with pytest.raises(ValueError):
        alloc.release([0])                 # null block is off-limits
    with pytest.raises(ValueError):
        alloc.release([spec.num_blocks])   # outside the pool


def test_cache_spec_sizing():
    spec = PagedCacheSpec.for_requests(3, max_tokens=20, block_size=8)
    assert spec.max_blocks_per_req == 3 and spec.max_tokens_per_req == 24
    assert spec.num_blocks == 1 + 3 * 3 + 1
    assert spec.blocks_needed(1) == 1 and spec.blocks_needed(17) == 3
    with pytest.raises(ValueError):
        PagedCacheSpec(num_blocks=1, block_size=4, max_blocks_per_req=1)


# ------------------------------------------------- int8 row quantization ---
def test_quantize_rows_deterministic_roundtrip():
    x = jax.random.normal(KEY, (3, 5, 7, 32), jnp.float32)
    q1, s1 = KC.quantize_rows(x)
    q2, s2 = KC.quantize_rows(x)
    assert jnp.array_equal(q1, q2) and jnp.array_equal(s1, s2)
    assert q1.shape == x.shape and s1.shape == x.shape[:-1] + (1,)
    back = KC.dequantize_rows(q1, s1)
    # round-to-nearest: error <= half a quantization step per row
    step = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    assert float(jnp.max(jnp.abs(back - x) - 0.5 * step)) <= 1e-6


# ------------------------------------ incremental decode == full forward ---
def _pool_cfg():
    from repro.config import ModelConfig
    return ModelConfig(name="t", family="dense", num_layers=3, d_model=16,
                       num_heads=4, num_kv_heads=2, d_ff=32, vocab_size=32,
                       param_dtype="float32")


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_append_token_writes_one_row_of_one_layer(quantized):
    """A token's K/V lands as one page-major row ``[Hkv * D]`` (head h in
    lanes h*D..) at (layer, block, offset), its int8 scales at lanes
    ``off * Hkv + h`` of the page's scale row; nothing else changes."""
    cfg = _pool_cfg()
    hkv, d = cfg.num_kv_heads, cfg.hd
    spec = PagedCacheSpec(num_blocks=6, block_size=4, max_blocks_per_req=2,
                          quantized=quantized)
    pools = KC.init_pools(cfg, spec)
    assert pools["k"].shape == (3, 6, 4, hkv * d)
    if quantized:
        assert pools["k_scale"].shape == (3, 6, 1, 4 * hkv)
    before = {k: np.asarray(p).copy() for k, p in pools.items()}
    k_tok = jax.random.normal(KEY, (2, hkv, d), jnp.float32)
    v_tok = -k_tok
    phys, off = jnp.asarray([3, 5], jnp.int32), jnp.asarray([1, 3],
                                                           jnp.int32)
    out = KC.append_token(pools, spec, 2, k_tok, v_tok, phys, off)
    for name, x in (("k", k_tok), ("v", v_tok)):
        want = before[name].copy()
        if quantized:
            q, sc = KC.quantize_rows(x)
            want[2, [3, 5], [1, 3]] = np.asarray(q).reshape(2, hkv * d)
            ws = before[name + "_scale"].copy()
            for n, (b, o) in enumerate(((3, 1), (5, 3))):
                ws[2, b, 0, o * hkv:(o + 1) * hkv] = np.asarray(sc)[n, :, 0]
            assert np.array_equal(np.asarray(out[name + "_scale"]), ws)
        else:
            want[2, [3, 5], [1, 3]] = np.asarray(x).reshape(2, hkv * d)
        assert np.array_equal(np.asarray(out[name]), want)


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_write_prefill_equals_token_appends(quantized):
    """Scattering a contiguous prefill [L, Hkv, S, D] into its blocks
    stores exactly what appending its tokens one layer at a time does."""
    cfg = _pool_cfg()
    hkv, d, s = cfg.num_kv_heads, cfg.hd, 7
    spec = PagedCacheSpec(num_blocks=6, block_size=4, max_blocks_per_req=2,
                          quantized=quantized)
    k = jax.random.normal(KEY, (3, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (3, hkv, s, d))
    table = jnp.asarray([4, 2], jnp.int32)
    got = KC.write_prefill(KC.init_pools(cfg, spec), spec, k, v, table)
    want = KC.init_pools(cfg, spec)
    pos = jnp.arange(s)
    phys, off = table[pos // 4], pos % 4
    for layer in range(3):
        want = KC.append_token(want, spec, layer,
                               k[layer].transpose(1, 0, 2),
                               v[layer].transpose(1, 0, 2), phys, off)
    for key in want:      # position 7 of block 2 is padding, zero in both
        assert np.array_equal(np.asarray(got[key]), np.asarray(want[key]))


@pytest.mark.parametrize("program", ["decode", "prefill_chunk", "verify",
                                     "copy_block", "write_prefill",
                                     "restore_rows"])
def test_engine_programs_donate_the_pools(dense_setup, program):
    """Each program that takes the pools consumes them: the arrays passed
    in are deleted and the returned pools are live and of the same
    geometry, so a launch allocates no second pool."""
    cfg, params = dense_setup
    spec = PagedCacheSpec.for_requests(2, 16, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=16, slots=2)
    pools = eng.init_pools()
    z = jnp.zeros(2, jnp.int32)
    tables = jnp.zeros((2, spec.max_blocks_per_req), jnp.int32)
    if program == "decode":
        out = eng.decode(params, pools, z, tables, z)[1]
    elif program == "prefill_chunk":
        out = eng.prefill_chunk(params, pools, jnp.zeros(4, jnp.int32),
                                tables[0], 0, 1)[1]
    elif program == "verify":
        out = eng.verify(params, pools, jnp.zeros((2, 2), jnp.int32),
                         tables, z, z)[1]
    elif program == "copy_block":
        out = eng.copy_block(pools, 0, 1)
    elif program == "restore_rows":      # the speculative rollback
        saved = KC.gather_rows(pools, z, z)
        out = eng.restore_rows(pools, saved, z, z)
    else:
        _, k, v = eng.prefill(params, *eng.pad_prompt([1, 2, 3]))
        out = eng.write_prefill(pools, k, v, tables[0])
    assert all(p.is_deleted() for p in pools.values())
    assert not any(p.is_deleted() for p in out.values())
    assert {k: p.shape for k, p in out.items()} == {
        k: p.shape for k, p in pools.items()}


@pytest.mark.parametrize("arch", ["flad_adllm", "xlstm_350m", "hymba_1_5b"])
def test_incremental_decode_matches_forward(arch):
    """prefill + N single-token serve steps must reproduce the logits of
    one full-sequence forward, per caching family (ring KV / ssm state /
    hybrid)."""
    from repro.core.steps import make_prefill_step, make_serve_step
    from repro.models import build_model

    cfg = _smoke_cfg(arch)
    batch, context, steps = 2, 8, 4
    shape = ShapeConfig("serve", context + steps, batch, "decode")
    model = build_model(cfg)
    params = model.init(KEY)
    prefill = jax.jit(make_prefill_step(cfg, shape))
    serve = jax.jit(make_serve_step(cfg, shape))
    tokens = jax.random.randint(jax.random.fold_in(KEY, 1),
                                (batch, context + steps), 0,
                                cfg.vocab_size, jnp.int32)

    state = model.init_state(batch, shape.seq_len)
    logits, state = prefill(params, {"tokens": tokens[:, :context]}, state)
    inc = [logits[:, -1]]
    for i in range(steps - 1):
        logits, state = serve(params, tokens[:, context + i:context + i + 1],
                              state, context + i)
        inc.append(logits[:, -1])

    # oracle: a fresh full forward (prefill of the whole prefix) per step
    for i, got in enumerate(inc):
        full, _ = prefill(params, {"tokens": tokens[:, :context + i]},
                          model.init_state(batch, shape.seq_len))
        assert float(jnp.max(jnp.abs(got - full[:, -1]))) < 2e-2, i


# -------------------------------------------- paged engine vs contiguous ---
def test_paged_engine_matches_contiguous(dense_setup):
    from repro.models import lm
    cfg, params = dense_setup
    spec = PagedCacheSpec.for_requests(2, 24, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=12, slots=2)
    alloc = BlockAllocator(spec)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9)]
    n_decode = 4

    pools = eng.init_pools()
    tables = np.zeros((2, spec.max_blocks_per_req), np.int32)
    ctx = np.zeros(2, np.int32)
    pend = np.zeros(2, np.int32)
    for i, p in enumerate(prompts):
        blocks = alloc.alloc(spec.blocks_needed(len(p) + n_decode))
        tables[i, :len(blocks)] = blocks
        toks, length = eng.pad_prompt(p)
        logits, k, v = eng.prefill(params, toks, length)
        pools = eng.write_prefill(pools, k, v, jnp.asarray(tables[i]))
        pend[i] = int(jnp.argmax(logits[0]))
        ctx[i] = len(p)
    streams = [[int(t)] for t in pend]
    for _ in range(n_decode - 1):
        logits, pools = eng.decode(params, pools, jnp.asarray(pend),
                                   jnp.asarray(tables), jnp.asarray(ctx))
        # a new array: on the CPU jnp.asarray may share the numpy buffer
        # with the decode still running, which an in-place += would race
        ctx = ctx + 1
        pend = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        for i in range(2):
            streams[i].append(int(pend[i]))

    # contiguous oracle: full forward over prompt + generated prefix
    for i, p in enumerate(prompts):
        toks = list(p)
        for step in range(n_decode):
            t = jnp.asarray(np.array(toks, np.int32))[None]
            logits_ref, _, _ = lm.forward(params, cfg, t,
                                          positions=jnp.arange(len(toks)))
            want = int(jnp.argmax(logits_ref[0, -1]))
            assert streams[i][step] == want, (i, step)
            toks.append(want)
    # and the final-step logits agree numerically per live lane
    t = jnp.asarray(np.array(list(prompts[1]) + streams[1][:-1],
                             np.int32))[None]
    logits_ref, _, _ = lm.forward(params, cfg, t,
                                  positions=jnp.arange(t.shape[1]))
    assert float(jnp.max(jnp.abs(logits[1] - logits_ref[0, -1]))) < 1e-3


def test_paged_engine_rejects_unsupported(dense_setup):
    cfg, _ = dense_setup
    spec = PagedCacheSpec.for_requests(1, 16, block_size=4)
    with pytest.raises(NotImplementedError):
        PagedEngine(_smoke_cfg("xlstm_350m"), spec, max_context=8, slots=1)
    with pytest.raises(ValueError):
        PagedEngine(cfg, spec, max_context=64, slots=1)


# ------------------------------------------------------- int8 cache mode ---
def test_int8_cache_drift_bounds(dense_setup):
    cfg, params = dense_setup
    requests = generate_fleet_requests("nano*1,agx*1", num_requests=3,
                                       max_prompt=6, seed=2,
                                       short_new=(3, 5), long_new=(8, 10),
                                       long_frac=0.4,
                                       vocab_size=cfg.vocab_size)
    rep = serve_continuous(cfg, params=params, slots=2, block_size=4,
                           max_context=12, num_requests=3,
                           fleet="nano*1,agx*1", max_prompt=6,
                           short_new=(3, 5), long_new=(8, 10),
                           long_frac=0.4, log_fn=None)
    fid = int8_cache_fidelity(cfg, params, requests, rep["sequences"],
                              block_size=4, max_context=12)
    # random-init logits are the worst case for argmax flips; the drift
    # bound is the real contract, the flip rate a sanity ceiling
    assert fid["max_logit_drift"] < 0.15
    assert fid["disagreement"] <= 0.15
    assert fid["positions"] == sum(len(s) for s in rep["sequences"].values())


# ---------------------------------------------- scheduler / loadgen -------
def _small_workload(cfg):
    return dict(fleet="nano*1,agx*1", num_requests=4, max_prompt=6,
                short_new=(3, 5), long_new=(9, 12), long_frac=0.5,
                slots=2, block_size=4, max_context=12, log_fn=None)


def test_continuous_equals_rebatch_streams(dense_setup):
    cfg, params = dense_setup
    opts = _small_workload(cfg)
    cont = serve_continuous(cfg, params=params, policy="continuous", **opts)
    reb = serve_continuous(cfg, params=params, policy="rebatch", **opts)
    assert cont["sequences"] == reb["sequences"]
    assert cont["decode_steps"] < reb["decode_steps"]
    assert cont["requests"] == reb["requests"] == 4


def test_scheduler_respects_block_cap(dense_setup):
    cfg, params = dense_setup
    spec = PagedCacheSpec.for_requests(2, 16, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=8, slots=2)
    sched = ContinuousScheduler(eng, params, max_inflight_blocks=4)
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(rid=i,
                         prompt=rng.integers(1, cfg.vocab_size,
                                             (6,)).astype(np.int32),
                         max_new_tokens=6) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    sched.step(0.0)
    # each request needs 3 blocks; the 4-block cap admits exactly one
    assert sched.num_active == 1
    assert sched.allocator.in_use <= 4
    done = []
    for step in range(1, 60):
        sched.step(float(step))
        if sched.idle:
            done = sched.finished
            break
    assert len(done) == 3                  # cap throttles, never starves
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert sched.allocator.in_use == 0     # every block returned


def test_loadgen_deterministic(dense_setup):
    cfg, params = dense_setup
    opts = _small_workload(cfg)
    a = serve_continuous(cfg, params=params, **opts)
    b = serve_continuous(cfg, params=params, **opts)
    assert a["sequences"] == b["sequences"]
    for key in ("decode_steps", "prefills", "p50_latency_s",
                "p99_latency_s", "deadline_hit_rate"):
        assert a[key] == b[key], key


def test_fleet_arrivals_follow_uplink():
    reqs = generate_fleet_requests("nano*1,agx*1", num_requests=2,
                                   max_prompt=8, seed=0)
    # same epoch; the agx's 2x faster V2X link must land no later than
    # the nano's for equal-or-shorter prompts (prompt lengths vary, so
    # compare normalized by payload)
    nano, agx = reqs[0], reqs[1]
    assert nano.arrival_s == pytest.approx(
        len(nano.prompt) * 64 / 0.125e9)
    assert agx.arrival_s == pytest.approx(len(agx.prompt) * 64 / 0.25e9)


# ---------------------------------------------- refcounted sharing --------
def _refcount_walk(alloc, spec, choices):
    """Mirror a random alloc/share/release walk against a pure-python
    refcount model; assert pool accounting after every op."""
    model, held = {}, []
    for op, salt in choices:
        if op == 0:
            n = 1 + salt % spec.max_blocks_per_req
            got = alloc.alloc(n)
            can = n <= (spec.num_blocks - 1) - len(model)
            assert (got is not None) == can
            for b in got or []:
                assert model.get(b, 0) == 0    # handed out from free
                model[b] = 1
                held.append(b)
        elif op == 1 and held:
            picks = [held[(salt + i) % len(held)]
                     for i in range(1 + salt % 3)]
            alloc.share(picks)
            for b in picks:
                model[b] += 1
                held.append(b)
        elif op == 2 and held:
            k = 1 + salt % min(6, len(held))
            idx = sorted({(salt + 7 * i) % len(held) for i in range(k)},
                         reverse=True)
            picks = [held[i] for i in idx]
            for i in idx:
                del held[i]
            alloc.release(picks)
            for b in picks:
                model[b] -= 1
                if model[b] == 0:
                    del model[b]
        assert alloc.free_blocks == (spec.num_blocks - 1) - len(model)
        for b in set(held):
            assert alloc.refcount(b) == model[b]
    # one release too many must raise and mutate nothing
    if held:
        b = held[0]
        extra = [b] * (model[b] + 1)
        free_before, rc_before = alloc.free_blocks, alloc.refcount(b)
        with pytest.raises(ValueError):
            alloc.release(extra)
        assert alloc.free_blocks == free_before
        assert alloc.refcount(b) == rc_before
    free = [b for b in range(1, spec.num_blocks) if b not in model]
    if free:
        with pytest.raises(ValueError):
            alloc.share([free[0]])             # share of a free block


def test_allocator_refcount_random_walk():
    spec = PagedCacheSpec(num_blocks=16, block_size=4, max_blocks_per_req=6)
    rng = np.random.default_rng(0)
    choices = [(int(rng.integers(0, 3)), int(rng.integers(0, 1 << 20)))
               for _ in range(300)]
    _refcount_walk(BlockAllocator(spec), spec, choices)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 1 << 20)),
                max_size=60))
def test_allocator_refcount_property(choices):
    spec = PagedCacheSpec(num_blocks=10, block_size=2, max_blocks_per_req=4)
    _refcount_walk(BlockAllocator(spec), spec, choices)


def test_prefix_cache_match_insert_evict():
    spec = PagedCacheSpec(num_blocks=12, block_size=4, max_blocks_per_req=4)
    alloc = BlockAllocator(spec)
    pc = PrefixCache(alloc)
    prompt = np.arange(1, 11, dtype=np.int32)     # 10 tokens, 2 full blocks
    assert pc.match(prompt) == ([], None, 0)      # cold miss
    blocks = alloc.alloc(3)
    pc.insert(prompt, blocks + [0])
    assert len(pc) == 2 and pc.registered_blocks == 2
    assert alloc.refcount(blocks[0]) == 2         # request + registry
    assert alloc.refcount(blocks[2]) == 1         # partial block never cached

    shared, cow, resume = pc.match(prompt)        # 8 of 10 tokens cached
    assert (shared, cow, resume) == (blocks[:2], None, 8)
    alloc.release(shared)
    div = np.concatenate([prompt[:4], prompt[:6][::-1]])
    shared, cow, resume = pc.match(div)           # diverges after block 0
    assert (shared, cow, resume) == ([blocks[0]], None, 4)
    alloc.release(shared)
    shared, cow, resume = pc.match(prompt[:8])    # whole prompt cached: CoW
    assert (shared, cow, resume) == ([blocks[0]], blocks[1], 7)
    alloc.release(shared + [cow])
    assert (pc.hits, pc.misses, pc.cached_tokens) == (3, 1, 19)

    alloc.release(blocks)                         # the request retires
    assert alloc.refcount(blocks[2]) == 0
    assert pc.evict(1) == 1                       # registry-only -> evictable
    assert pc.evict(10) == 1
    assert len(pc) == 0 and alloc.in_use == 0


# ------------------------------- chunked prefill / stream equivalence -----
def _trace(cfg, n=4, seed=0, max_prompt=6):
    return generate_fleet_requests("nano*1,agx*1", num_requests=n,
                                   max_prompt=max_prompt, seed=seed,
                                   short_new=(3, 5), long_new=(9, 12),
                                   long_frac=0.5, vocab_size=cfg.vocab_size)


def _assert_streams_greedy_consistent(cfg, params, requests, sequences):
    """Each stream must be self-consistent under ONE full lm.forward over
    prompt + generated tokens (exact for greedy by the prefix property)."""
    from repro.models import lm
    for r in requests:
        stream = sequences[r.rid]
        toks = np.concatenate([r.prompt, np.asarray(stream, np.int32)])
        logits, _, _ = lm.forward(params, cfg, jnp.asarray(toks)[None],
                                  positions=jnp.arange(len(toks)))
        plen = len(r.prompt)
        for i, tok in enumerate(stream):
            assert int(jnp.argmax(logits[0, plen - 1 + i])) == tok, \
                (r.rid, i)


def test_chunked_equals_monolithic_and_oracle(dense_setup):
    cfg, params = dense_setup
    reqs = _trace(cfg)
    base = dict(params=params, slots=2, block_size=4, max_context=12,
                requests=reqs, log_fn=None)
    mono = serve_continuous(cfg, prefill="monolithic", **base)
    assert mono["prefills"] > 0 and mono["prefill_chunks"] == 0
    for chunk in (3, 16):        # uneven chunking and one-shot chunking
        ch = serve_continuous(cfg, prefill="chunked", prefill_chunk=chunk,
                              **base)
        assert ch["sequences"] == mono["sequences"], chunk
        assert ch["prefills"] == 0 and ch["prefill_chunks"] > 0
    _assert_streams_greedy_consistent(cfg, params, reqs, mono["sequences"])


def test_chunked_int8_fidelity(dense_setup):
    """The int8 drift contract holds through the chunked prefill path."""
    cfg, params = dense_setup
    reqs = _trace(cfg, n=3, seed=2)
    rep = serve_continuous(cfg, params=params, prefill="chunked",
                           prefill_chunk=4, requests=reqs, slots=2,
                           block_size=4, max_context=12, log_fn=None)
    fid = int8_cache_fidelity(cfg, params, reqs, rep["sequences"],
                              block_size=4, max_context=12,
                              prefill="chunked", prefill_chunk=4)
    assert fid["max_logit_drift"] < 0.15
    assert fid["disagreement"] <= 0.15


def test_prefill_burst_keeps_decode_lanes_live(dense_setup):
    """8-request burst: at most ONE prefill unit per step in either mode,
    decode lanes keep emitting while later arrivals are still
    prefilling, and the two modes agree on every stream."""
    cfg, params = dense_setup
    spec = PagedCacheSpec.for_requests(4, 16, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=8, slots=4)

    def mk():
        rng = np.random.default_rng(1)
        return [ServeRequest(rid=i,
                             prompt=rng.integers(1, cfg.vocab_size,
                                                 (6,)).astype(np.int32),
                             max_new_tokens=8) for i in range(8)]

    streams = {}
    for mode, kw in (("chunked", dict(prefill_chunk=2)), ("monolithic", {})):
        sched = ContinuousScheduler(eng, params, prefill=mode, **kw)
        for r in mk():
            sched.submit(r)
        overlap, prev_units = 0, 0
        for step in range(400):
            emitted = sched.step(float(step))
            units = sched.prefills_run + sched.prefill_chunks_run
            assert units - prev_units <= 1, (mode, step)
            prev_units = units
            still = any(sched.active[i] is not None
                        and not sched.prefill_done[i]
                        for i in range(sched.slots))
            if emitted > 0 and still:
                overlap += 1
            if sched.idle:
                break
        assert sched.idle and len(sched.finished) == 8
        assert overlap > 0, mode           # decode ran during the burst
        assert sched.allocator.in_use == 0
        streams[mode] = {r.rid: list(r.tokens) for r in sched.finished}
    assert streams["chunked"] == streams["monolithic"]


def test_chunked_lifts_max_context_submit_limit(dense_setup):
    """Chunked mode accepts prompts beyond the monolithic prefill bucket
    (bounded only by table capacity) and still streams correctly."""
    cfg, params = dense_setup
    spec = PagedCacheSpec.for_requests(1, 24, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=8, slots=1)
    rng = np.random.default_rng(5)
    long_prompt = rng.integers(1, cfg.vocab_size, (14,)).astype(np.int32)

    mono = ContinuousScheduler(eng, params, prefill="monolithic")
    with pytest.raises(ValueError):        # 14 > max_context=8
        mono.submit(ServeRequest(rid=0, prompt=long_prompt,
                                 max_new_tokens=4))
    ch = ContinuousScheduler(eng, params, prefill="chunked",
                             prefill_chunk=8)
    with pytest.raises(ValueError):        # 22+4 > 24-token table
        ch.submit(ServeRequest(rid=1,
                               prompt=rng.integers(
                                   1, cfg.vocab_size,
                                   (22,)).astype(np.int32),
                               max_new_tokens=4))
    req = ServeRequest(rid=0, prompt=long_prompt, max_new_tokens=4)
    done = ch.run_to_completion([req])
    assert len(done) == 1 and len(done[0].tokens) == 4
    _assert_streams_greedy_consistent(cfg, params, [req],
                                      {0: list(done[0].tokens)})


def test_moe_family_through_scheduler():
    """MoE configs serve through the chunked continuous scheduler over
    the dropless held-expert layer (smoke + determinism; monolithic
    prefill, which runs the training forward's capacity routing, refuses
    them)."""
    from repro.models import lm
    cfg = _smoke_cfg("qwen3_moe_30b_a3b")
    params = lm.init(KEY, cfg)
    reqs = _trace(cfg, n=3, seed=1)
    kw = dict(params=params, prefill="chunked", prefill_chunk=4,
              requests=reqs, slots=2, block_size=4, max_context=12,
              log_fn=None)
    a = serve_continuous(cfg, **kw)
    b = serve_continuous(cfg, **kw)
    assert a["requests"] == 3 and a["total_new_tokens"] > 0
    assert a["sequences"] == b["sequences"]
    with pytest.raises(NotImplementedError, match="chunked"):
        serve_continuous(cfg, **dict(kw, prefill="monolithic"))


# ----------------------------------------- pod prefix-cache sharing -------
def test_prefix_sharing_streams_and_block_immutability(dense_setup):
    """Prefix sharing must not change any stream, and registered template
    blocks must be bit-identical after other requests mapped them
    (shared blocks are read-only; the CoW path covers the whole-prompt
    case)."""
    cfg, params = dense_setup
    rng = np.random.default_rng(9)
    template = rng.integers(1, cfg.vocab_size, (8,)).astype(np.int32)
    sfx = [rng.integers(1, cfg.vocab_size, (2,)).astype(np.int32)
           for _ in range(2)]

    def mk():
        return [
            ServeRequest(rid=0, prompt=np.concatenate([template, sfx[0]]),
                         max_new_tokens=4),
            ServeRequest(rid=1, prompt=np.concatenate([template, sfx[1]]),
                         max_new_tokens=4),
            ServeRequest(rid=2, prompt=template.copy(),   # CoW: whole
                         max_new_tokens=4),                # prompt cached
        ]

    spec = PagedCacheSpec.for_requests(2, 16, block_size=4, headroom=4)
    eng = PagedEngine(cfg, spec, max_context=12, slots=2)

    base = ContinuousScheduler(eng, params, prefill="chunked",
                               prefill_chunk=4)
    want = {r.rid: list(r.tokens)
            for r in base.run_to_completion(mk())}

    sched = ContinuousScheduler(eng, params, prefill="chunked",
                                prefill_chunk=4, prefix_cache=True)
    reqs = mk()
    first = sched.run_to_completion([reqs[0]])
    assert sched.prefix.registered_blocks == 2    # template = 2 full blocks
    reg = sorted(set(sched.prefix._map.values()))
    snap = np.asarray(sched.pools["k"])[:, reg].copy()

    rest = sched.run_to_completion(reqs[1:])
    got = {r.rid: list(r.tokens) for r in first + rest}
    assert got == want
    assert sched.prefix.hits >= 2                 # rid 1 shares, rid 2 CoWs
    assert sched.prefix.shared_blocks > 0
    # registered template blocks were mapped, never rewritten
    assert np.array_equal(snap, np.asarray(sched.pools["k"])[:, reg])
    # after drain only the registry holds blocks
    assert sched.allocator.in_use == sched.prefix.registered_blocks


def test_pod_trace_prefix_report(dense_setup):
    cfg, params = dense_setup
    reqs = generate_pod_requests("nano*1,agx*1", num_requests=6, pods=1,
                                 template_len=8, max_suffix=4, seed=0,
                                 short_new=(3, 4), long_new=(5, 6),
                                 long_frac=0.5, vocab_size=cfg.vocab_size)
    base = dict(params=params, prefill="chunked", prefill_chunk=4,
                requests=reqs, slots=2, block_size=4, max_context=16,
                log_fn=None)
    on = serve_continuous(cfg, prefix_cache=True, **base)
    off = serve_continuous(cfg, prefix_cache=False, **base)
    assert on["sequences"] == off["sequences"]
    assert on["prefix_hits"] > 0 and on["prefix_blocks_saved"] > 0
    assert 0 < on["prefix_hit_rate"] <= 1
    assert "prefix_hits" not in off
    # sharing strictly reduces the prefill work actually issued
    assert on["prefill_padded_tokens"] < off["prefill_padded_tokens"]


def test_ttft_and_queue_wait_in_report(dense_setup):
    cfg, params = dense_setup
    rep = serve_continuous(cfg, params=params, requests=_trace(cfg),
                           slots=2, block_size=4, max_context=12,
                           prefill_cost=PrefillCostModel(), log_fn=None)
    assert 0 < rep["p50_ttft_s"] <= rep["p50_latency_s"]
    assert rep["p99_ttft_s"] >= rep["p50_ttft_s"]
    assert rep["p99_queue_wait_s"] >= rep["p50_queue_wait_s"] >= 0
    assert rep["p50_ttft_s"] >= rep["p50_queue_wait_s"]


# ------------------------------------- speculative decoding (draft-verify)
def _spec_trace(cfg, n=6, seed=3):
    return generate_pod_requests("nano*1,agx*1", num_requests=n, pods=2,
                                 template_len=8, max_suffix=4, seed=seed,
                                 short_new=(3, 6), long_new=(8, 12),
                                 long_frac=0.4, vocab_size=cfg.vocab_size)


@pytest.mark.parametrize("cache", ["fp32", "int8"])
def test_speculative_streams_bit_identical(dense_setup, cache):
    """Draft-verify speculation must not change a single emitted token —
    self-drafting (acceptance 1.0) and an unrelated random draft
    (acceptance ~0, every speculative step rolls back) both reproduce
    the non-speculative greedy streams bitwise, in fp32 AND int8 cache
    mode, while speculation still wins sim time at high acceptance."""
    from repro.models import lm
    from repro.serve import SpecDecodeCostModel
    cfg, params = dense_setup
    common = dict(params=params, slots=2, block_size=4, max_context=16,
                  prefill="chunked", prefill_chunk=4, prefix_cache=True,
                  cache=cache, requests=_spec_trace(cfg), log_fn=None,
                  warm_passes=1)
    base = serve_continuous(cfg, prefill_cost=PrefillCostModel(), **common)
    spec = serve_continuous(cfg, speculative=True, draft_k=3,
                            prefill_cost=SpecDecodeCostModel(), **common)
    assert spec["sequences"] == base["sequences"]
    assert spec["spec_steps"] > 0
    assert spec["acceptance_rate"] == 1.0       # self-draft agrees always
    assert spec["decode_steps"] < base["decode_steps"]
    assert spec["sim_time_s"] < base["sim_time_s"]
    # unrelated draft weights: every draft rejected, rollback must leave
    # the pools indistinguishable from never having drafted -> streams
    # still bitwise equal (a single corrupt K/V row would cascade)
    rej = serve_continuous(cfg, speculative=True, draft_k=3,
                           draft_params=lm.init(jax.random.PRNGKey(7), cfg),
                           prefill_cost=SpecDecodeCostModel(), **common)
    assert rej["sequences"] == base["sequences"]
    assert rej["acceptance_rate"] < 0.2
    assert rej["proposed_drafts"] > 0


def test_speculative_validation(dense_setup):
    cfg, params = dense_setup
    spec = PagedCacheSpec.for_requests(1, 16, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=8, slots=1)
    with pytest.raises(ValueError):             # greedy-only by definition
        ContinuousScheduler(eng, params, speculative=True,
                            sampling="temperature")
    with pytest.raises(ValueError):             # resume needs chunked
        ContinuousScheduler(eng, params, prefill="monolithic",
                            preemption=True)
    with pytest.raises(ValueError):             # draft_k >= 1
        ContinuousScheduler(eng, params, speculative=True, draft_k=0)
    # speculative + monolithic is allowed, preemption just defaults off
    s = ContinuousScheduler(eng, params, speculative=True,
                            prefill="monolithic")
    assert s.speculative and not s.preemption


def _rollback_cycle(salt, quantized):
    """Draft-append-then-reject cycle must restore the pools bitwise
    (fp32 and int8 — codes AND scales); a partial accept restores
    exactly the rejected tail while leaving accepted rows."""
    from repro.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=8,
                      num_heads=2, num_kv_heads=1, d_ff=16, vocab_size=32,
                      param_dtype="float32")
    spec = PagedCacheSpec(num_blocks=5, block_size=4, max_blocks_per_req=4,
                          quantized=quantized)
    rng = np.random.default_rng(salt)
    pools = KC.init_pools(cfg, spec)
    if quantized:
        pools = {
            "k": jnp.asarray(rng.integers(-127, 128, pools["k"].shape),
                             jnp.int8),
            "v": jnp.asarray(rng.integers(-127, 128, pools["v"].shape),
                             jnp.int8),
            "k_scale": jnp.asarray(rng.random(pools["k_scale"].shape),
                                   jnp.float32),
            "v_scale": jnp.asarray(rng.random(pools["v_scale"].shape),
                                   jnp.float32)}
    else:
        pools = {k: jnp.asarray(rng.standard_normal(p.shape), p.dtype)
                 for k, p in pools.items()}
    before = {k: np.asarray(p).copy() for k, p in pools.items()}

    # a draft window somewhere in blocks 1..4
    w = int(rng.integers(1, 9))
    start = int(rng.integers(0, 16 - w))
    pos = np.arange(start, start + w)
    phys = jnp.asarray(1 + pos // spec.block_size, jnp.int32)
    off = jnp.asarray(pos % spec.block_size, jnp.int32)

    saved = KC.gather_rows(pools, phys, off)
    garbage = {k: jnp.asarray(rng.standard_normal(r.shape), r.dtype)
               if not np.issubdtype(np.asarray(r).dtype, np.integer)
               else jnp.asarray(rng.integers(-127, 128, r.shape), r.dtype)
               for k, r in saved.items()}
    pools = KC.scatter_rows(pools, garbage, phys, off)   # the draft append
    assert any(not np.array_equal(np.asarray(pools[k]), before[k])
               for k in pools)

    accepted = int(rng.integers(0, w + 1))
    # kept positions redirect to the null block: garbage lands in block 0
    keep = np.arange(w) < accepted
    r_phys = jnp.asarray(np.where(keep, 0, np.asarray(phys)), jnp.int32)
    r_off = jnp.asarray(np.where(keep, 0, np.asarray(off)), jnp.int32)
    pools = KC.scatter_rows(pools, saved, r_phys, r_off)
    for k in pools:
        got = np.asarray(pools[k])
        # expected pool: pristine everywhere except the accepted rows,
        # which keep the drafted values (their tokens were emitted)
        want = before[k].copy()
        if accepted:
            ap, ao = np.asarray(phys)[:accepted], np.asarray(off)[:accepted]
            g = np.asarray(garbage[k])[:, :accepted]       # [L, a, ...]
            if k.endswith("_scale"):        # page rows [L, NB, 1, bs*Hkv]
                hkv = g.shape[-1]
                lanes = ao[:, None] * hkv + np.arange(hkv)
                want[:, ap[:, None], 0, lanes] = g
            else:                           # token rows [L, NB, bs, Hkv*D]
                want[:, ap, ao] = g
        # block 0 is garbage by contract; everything else must be exact
        assert np.array_equal(got[:, 1:], want[:, 1:]), k


@pytest.mark.parametrize("quantized", [False, True])
def test_draft_rollback_bitwise_walk(quantized):
    rng = np.random.default_rng(11)
    for _ in range(20):
        _rollback_cycle(int(rng.integers(0, 1 << 20)), quantized)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 1 << 20), st.booleans())
def test_draft_rollback_bitwise_property(salt, quantized):
    _rollback_cycle(salt, quantized)


def test_prefix_evict_never_drops_shared_blocks():
    """Satellite regression: ``PrefixCache.evict`` must skip any block a
    live request still holds (refcount > 1) — evicting it would hand a
    mapped, readable block back to the allocator for reuse."""
    spec = PagedCacheSpec(num_blocks=12, block_size=4, max_blocks_per_req=4)
    alloc = BlockAllocator(spec)
    pc = PrefixCache(alloc)
    prompt = np.arange(1, 9, dtype=np.int32)       # 2 full blocks
    blocks = alloc.alloc(2)
    pc.insert(prompt, blocks + [0, 0])
    shared, cow, resume = pc.match(prompt[:8])     # CoW hold on block 1
    held = shared + [cow]
    assert alloc.refcount(blocks[0]) == 3          # request+registry+match
    assert pc.evict(10) == 0                       # all entries are shared
    assert len(pc) == 2 and alloc.refcount(blocks[0]) == 3
    alloc.release(held)
    alloc.release(blocks)                          # the request retires
    assert pc.evict(10) == 2                       # now registry-only
    assert alloc.in_use == 0


def test_preemption_resume_exact(dense_setup):
    """A tight pool + a later-but-tighter-deadline arrival preempts the
    live lane; the victim's resume replays through the prefix cache and
    its stream stays bit-identical to an unpressured run."""
    cfg, params = dense_setup
    rng = np.random.default_rng(4)
    pa = rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32)
    pb = rng.integers(1, cfg.vocab_size, (6,)).astype(np.int32)

    def mk():
        return [ServeRequest(rid=0, prompt=pa.copy(), max_new_tokens=8,
                             deadline_s=100.0),
                ServeRequest(rid=1, prompt=pb.copy(), max_new_tokens=4,
                             deadline_s=1.0)]

    spec = PagedCacheSpec.for_requests(2, 16, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=8, slots=2)
    oracle = ContinuousScheduler(eng, params, prefill="chunked",
                                 prefill_chunk=4, prefix_cache=True)
    want = {r.rid: list(r.tokens) for r in oracle.run_to_completion(mk())}

    # each request needs 4 blocks; a 5-block cap cannot host both
    sched = ContinuousScheduler(eng, params, prefill="chunked",
                                prefill_chunk=4, prefix_cache=True,
                                preemption=True, max_inflight_blocks=5)
    ra, rb = mk()
    sched.submit(ra)
    for step in range(4):               # admit + prefill A, decode a bit
        sched.step(float(step))
        sched.flush_trace(step + 1.0)
    assert len(ra.tokens) > 0 and not sched.idle
    sched.submit(rb)
    steps = 4
    while not sched.idle:
        sched.step(float(steps))
        sched.flush_trace(steps + 1.0)
        steps += 1
        assert steps < 200
    got = {r.rid: list(r.tokens) for r in sched.finished}
    assert got == want
    assert sched.preemptions == 1
    assert [r.rid for r in sched.finished] == [1, 0]   # B jumped the line
    # the victim's re-registered chain is what remains allocated
    assert sched.allocator.in_use == sched.prefix.registered_blocks
    m = sched.metrics.snapshot()["metrics"]
    assert m["serve_preemptions"]["series"][0]["value"] == 1.0
    # without a strictly-lower-priority victim nothing is preempted: the
    # same pressure with deadlines flipped just queues the newcomer
    s2 = ContinuousScheduler(eng, params, prefill="chunked",
                             prefill_chunk=4, prefix_cache=True,
                             preemption=True, max_inflight_blocks=5)
    ra2 = ServeRequest(rid=0, prompt=pa.copy(), max_new_tokens=8,
                       deadline_s=1.0)
    rb2 = ServeRequest(rid=1, prompt=pb.copy(), max_new_tokens=4,
                       deadline_s=100.0)
    s2.submit(ra2)
    for step in range(4):
        s2.step(float(step))
        s2.flush_trace(step + 1.0)
    s2.submit(rb2)
    steps = 4
    while not s2.idle:
        s2.step(float(steps))
        s2.flush_trace(steps + 1.0)
        steps += 1
        assert steps < 200
    assert s2.preemptions == 0
    assert [r.rid for r in s2.finished] == [0, 1]


def test_unstarted_request_report_none(dense_setup):
    """Satellite: a request that never produced a token reports None for
    ttft/queue-wait (not stale zeros), and the loadgen's deadline hit
    rate scores only requests that started."""
    cfg, params = dense_setup
    r = ServeRequest(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                     max_new_tokens=2)
    assert r.ttft_s is None and r.queue_wait_s is None
    assert r.latency_s is None and not r.met_deadline
    rep = serve_continuous(cfg, params=params, requests=_trace(cfg),
                           slots=2, block_size=4, max_context=12,
                           prefill_cost=PrefillCostModel(), log_fn=None)
    assert rep["unstarted_requests"] == 0       # a drained trace all ran
    assert 0 <= rep["deadline_hit_rate"] <= 1


# ----------------------------------------------------- session plumbing ---
def test_session_serve_continuous_smoke():
    from repro.api import MeshSpec, Session
    ses = Session("flad-adllm", strategy="tensor",
                  mesh=MeshSpec((1,), axes=("data",), devices=1))
    out = ses.serve(scheduler="continuous", requests=3, batch=2,
                    context=12, block_size=4, max_prompt=6,
                    short_new=(3, 4), long_new=(6, 8), log_fn=None)
    assert out["requests"] == 3
    assert out["total_new_tokens"] > 0
    assert out["warm_tokens_per_s"] > 0
    spec = ses.serve(scheduler="continuous", requests=3, batch=2,
                     context=12, block_size=4, max_prompt=6,
                     short_new=(3, 4), long_new=(6, 8),
                     speculative=True, draft_k=2, log_fn=None)
    assert spec["sequences"] == out["sequences"]  # bit-identical via API too
    assert spec["acceptance_rate"] == 1.0         # default self-draft
    with pytest.raises(ValueError):
        ses.serve(scheduler="bogus")
    with pytest.raises(ValueError):
        ses.serve(speculative=True)               # legacy can't speculate
    with pytest.raises(ValueError):
        ses.serve(scheduler="continuous", draft_pod=0)  # needs speculative
    with pytest.raises(ValueError):               # tensor has no pod view
        ses.serve(scheduler="continuous", speculative=True, draft_pod=0)


def test_legacy_serve_sampling():
    from repro.api.serving import serve_requests
    cfg = _smoke_cfg()
    kw = dict(batch=2, context=8, decode_steps=3, requests=1, log_fn=None)
    g1 = serve_requests(cfg, key=jax.random.PRNGKey(5), **kw)
    g2 = serve_requests(cfg, key=jax.random.PRNGKey(5), **kw)
    assert jnp.array_equal(g1["sequences"][0], g2["sequences"][0])
    t1 = serve_requests(cfg, key=jax.random.PRNGKey(5),
                        sampling="temperature", temperature=1.5, **kw)
    t2 = serve_requests(cfg, key=jax.random.PRNGKey(5),
                        sampling="temperature", temperature=1.5, **kw)
    t3 = serve_requests(cfg, key=jax.random.PRNGKey(6),
                        sampling="temperature", temperature=1.5, **kw)
    assert jnp.array_equal(t1["sequences"][0], t2["sequences"][0])
    assert not jnp.array_equal(t1["sequences"][0], t3["sequences"][0])
    assert "warm_tokens_per_s" in g1 and g1["warm_tokens_per_s"] > 0
    with pytest.raises(ValueError):
        serve_requests(cfg, sampling="nucleus", **kw)
