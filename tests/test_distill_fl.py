"""Federated personalized distillation: the ``distill_fl`` strategy,
the fused LoRA forward behind it, adapter-delta codec roundtrips, and
the per-pod serving handoff."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.common import reduced
from repro.distill import lora as L
from repro.distill.celladapt import adllm_config, init_adllm
from repro.models import lm

KEY = jax.random.PRNGKey(0)


def _acfg():
    return adllm_config(reduced(get_config("flad_adllm")), feature_dim=32,
                        feature_tokens=8, num_waypoints=6)


@pytest.fixture(scope="module")
def adllm():
    cfg = _acfg()
    return cfg, init_adllm(KEY, cfg)


# -------------------------------------------------- init_lora regression ---
def test_init_lora_no_match_raises(adllm):
    """Regression: targets matching nothing used to return an all-None
    tree — a silent fine-tuning no-op."""
    cfg, params = adllm
    with pytest.raises(ValueError, match="match no parameter leaf"):
        L.init_lora(KEY, params, L.LoRAConfig(targets=("nope",)))
    # the error names what IS adaptable
    with pytest.raises(ValueError, match="wq"):
        L.init_lora(KEY, params, L.LoRAConfig(targets=("bogus",)))


def test_init_merge_determinism(adllm):
    cfg, params = adllm
    lcfg = L.LoRAConfig(rank=4, alpha=8.0)
    f1 = L.init_lora(KEY, params, lcfg)
    f2 = L.init_lora(KEY, params, lcfg)
    for a, b in zip(jax.tree.leaves(f1), jax.tree.leaves(f2)):
        assert jnp.array_equal(a, b)
    m1 = L.merge_lora(params, f1, lcfg)
    m2 = L.merge_lora(params, f2, lcfg)
    for a, b in zip(jax.tree.leaves(m1), jax.tree.leaves(m2)):
        assert jnp.array_equal(a, b)
    # B zero-init: merging a fresh adapter is the identity
    for p, m in zip(jax.tree.leaves(params), jax.tree.leaves(m1)):
        assert jnp.allclose(p, m)


# ----------------------------------- fused adapted forward == merge_lora ---
def test_fused_forward_matches_merged(adllm):
    """lm.forward(lora=...) through the fused base+low-rank kernel must
    match the forward of merge_lora-folded params, and gradients must
    reach every factor."""
    cfg, params = adllm
    lcfg = L.LoRAConfig(rank=4, alpha=8.0)
    factors = L.init_lora(jax.random.fold_in(KEY, 1), params, lcfg)
    # randomize B so the adapter actually perturbs the forward
    factors = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.fold_in(KEY, 2),
                                               x.shape), factors)
    toks = jax.random.randint(jax.random.fold_in(KEY, 3), (2, 12), 0,
                              cfg.vocab_size)
    ref, _, _ = lm.forward(L.merge_lora(params, factors, lcfg), cfg, toks)
    fused, _, _ = lm.forward(params, cfg, toks, lora=factors,
                             lora_scale=lcfg.scale)
    assert float(jnp.abs(ref - fused).max()) < 1e-3

    def loss(f):
        out, _, _ = lm.forward(params, cfg, toks, lora=f,
                               lora_scale=lcfg.scale)
        return (out ** 2).mean()

    grads = jax.grad(loss)(factors)
    for g in jax.tree.leaves(grads):
        assert float(jnp.abs(g).sum()) > 0.0


def test_fused_forward_rejects_non_block_factors(adllm):
    """Factors outside the scanned block stack (embed/head) have no fused
    path — must fail loudly, not silently ignore the adapter."""
    cfg, params = adllm
    lcfg = L.LoRAConfig(rank=2, targets=("w",))   # head/projector "w" leaves
    factors = L.init_lora(KEY, params, lcfg)
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="block stack"):
        lm.forward(params, cfg, toks, lora=factors, lora_scale=lcfg.scale)


# -------------------------------- adapter deltas through the comm fabric ---
def test_factor_codec_roundtrip_error_feedback(adllm):
    """int8 + error feedback on client-stacked factor trees: one round is
    within the quantization bound, and the residual carries what was
    lost so two half-updates converge to the true sum."""
    from repro.comm.codecs import get_codec, roundtrip_stacked, zero_residual
    cfg, params = adllm
    lcfg = L.LoRAConfig(rank=4)
    factors = L.init_lora(KEY, params, lcfg)
    C = 3
    deltas = jax.tree.map(
        lambda x: 0.1 * jax.random.normal(
            jax.random.fold_in(KEY, 7), (C,) + x.shape, jnp.float32),
        factors)
    codec = get_codec("int8")
    residual = zero_residual(deltas)
    decoded, residual = roundtrip_stacked(codec, deltas, residual, KEY)
    # tree structure survives (None leaves stay None)
    assert jax.tree_util.tree_structure(decoded) == \
        jax.tree_util.tree_structure(deltas)
    for d, x in zip(jax.tree.leaves(decoded), jax.tree.leaves(deltas)):
        step = jnp.abs(x).max() / 127.0
        assert float(jnp.abs(d - x).max()) <= float(step) + 1e-6
    # error feedback: residual == what the wire lost, exactly
    for r, d, x in zip(jax.tree.leaves(residual), jax.tree.leaves(decoded),
                       jax.tree.leaves(deltas)):
        assert jnp.allclose(r, x - d, atol=1e-6)
    # second identical round re-injects the loss: cumulative decode gets
    # closer to the cumulative truth than 2x the one-shot bound
    decoded2, _ = roundtrip_stacked(codec, deltas, residual,
                                    jax.random.fold_in(KEY, 9))
    for d1, d2, x in zip(jax.tree.leaves(decoded), jax.tree.leaves(decoded2),
                         jax.tree.leaves(deltas)):
        step = jnp.abs(x).max() / 127.0
        err = jnp.abs((d1 + d2) - 2 * x).max()
        assert float(err) <= 1.5 * float(step) + 1e-6


def test_pod_slice_broadcast_roundtrip():
    from repro.comm.hierarchy import pod_broadcast, pod_slice
    from repro.comm.topology import parse_topology
    topo = parse_topology("2@nano*2,agx*2")
    edge = {"a": jnp.arange(2 * 3, dtype=jnp.float32).reshape(2, 3),
            "b": None}
    clients = pod_broadcast(edge, topo)
    assert clients["a"].shape == (topo.n_clients, 3)
    for c in range(topo.n_clients):
        e = int(topo.client_edge[c])
        assert jnp.array_equal(clients["a"][c], edge["a"][e])
    back = pod_slice(clients, topo)
    assert jnp.array_equal(back["a"], edge["a"])


# ---------------------------------------------- end-to-end through Session --
@pytest.fixture(scope="module")
def distill_session():
    from repro.api import MeshSpec, Session
    from repro.train.loop import LoopHooks
    quiet = LoopHooks(log_every=1000, log_fn=lambda *a, **k: None)
    sess = Session("flad-adllm", shape="16x8",
                   mesh=MeshSpec.parse("2", devices=2),
                   strategy="distill_fl", learning_rate=3e-2, seed=0,
                   hooks=quiet, topology="2@nano*2", codec="int8",
                   local_steps=2, lora_rank=4, kd_weight=0.1, mix=0.25,
                   warmup_steps=30, beta=0.05, samples_per_vehicle=128,
                   heldout=64)
    out = sess.run(8)
    return sess, out


def test_session_distill_fl_adapter_uplink_20x(distill_session):
    """Adapter-only uplinks must be >= 20x smaller than full-delta
    hier_fl rounds on the same arch/topology/codec."""
    from repro.api.strategies import get_strategy
    sess, out = distill_session
    up = sess.strategy.comm_stats["uplink_bytes"]
    hier = get_strategy("hier_fl", topology="2@nano*2", codec="int8")
    full_up = hier._round_stats(sess.cfg)["uplink_bytes"]
    assert full_up / up >= 20.0, (full_up, up)
    # and the wire metrics ride along in every round's history
    assert out["history"][-1]["comm_bytes_up"] == float(up)
    assert out["history"][-1]["comm_bytes_backhaul"] > 0


def test_session_distill_fl_personalization(distill_session):
    """Each pod's student (base + pod adapter) beats the global model
    (base + cloud-merged adapter) on its own pod's held-out partition."""
    from repro.distill.federated import waypoint_eval
    sess, _ = distill_session
    st = sess.strategy
    acfg = st.adllm_cfg(sess.cfg)
    _, held, _ = st.datasets(sess.cfg, sess.shape)
    global_model = sess.merged_params()
    for e in range(len(held)):
        pod_model = st.pod_params(sess.state, e)
        g = waypoint_eval(global_model, acfg, held[e])
        p = waypoint_eval(pod_model, acfg, held[e])
        assert p < g, (e, p, g)


def test_session_distill_fl_state_and_training(distill_session):
    """Composite state survives the loop: frozen base, per-pod factors
    that actually moved, and a supervised warmup that learned."""
    sess, out = distill_session
    st = sess.strategy
    params_like = sess.state[0]
    assert set(params_like) == {"base", "factors"}
    assert st.warmup_history[-1] < st.warmup_history[0]
    # pod members share an adapter; pods differ (personalization)
    f = params_like["factors"]
    a = jax.tree.leaves(f)[0]
    topo = st.topology
    m0 = np.asarray(topo.member_indices[0])
    m1 = np.asarray(topo.member_indices[1])
    assert jnp.allclose(a[m0[0]], a[m0[-1]])
    assert not jnp.allclose(a[m0[0]], a[m1[0]])
    # factors moved off zero-B init
    assert float(jnp.abs(a[0]).sum()) > 0.0


# ------------------------------------------------- personalized serving ----
def test_pod_serving_matches_merged_oracle(distill_session):
    """A pod's merged adapter serves through PagedEngine with greedy
    streams identical to the merged-params lm.forward oracle."""
    from repro.serve import BlockAllocator, PagedCacheSpec, PagedEngine
    sess, _ = distill_session
    params = sess.strategy.pod_params(sess.state, 0)
    cfg = sess.cfg
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

    for i, p in enumerate(prompts):
        toks = list(p)
        for step in range(n_decode):
            t = jnp.asarray(np.array(toks, np.int32))[None]
            ref, _, _ = lm.forward(params, cfg, t,
                                   positions=jnp.arange(len(toks)))
            want = int(jnp.argmax(ref[0, -1]))
            assert streams[i][step] == want, (i, step)
            toks.append(want)


def test_session_serve_pod_continuous(distill_session):
    """Session.serve(pod=...) hands the personalized model to the
    continuous-batching tier end to end."""
    sess, _ = distill_session
    out = sess.serve(pod=1, scheduler="continuous", requests=2, batch=2,
                     context=16, log_fn=lambda *a, **k: None,
                     max_prompt=8, short_new=(2, 4), long_frac=0.0)
    assert out["requests"] == 2 and out["total_new_tokens"] > 0
    with pytest.raises(ValueError, match="pod"):
        sess.serve(pod=0, params={},
                   log_fn=lambda *a, **k: None)
