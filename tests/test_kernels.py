"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype, k):
    x = jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,window",
    [
        (2, 4, 2, 128, 128, 64, True, None),      # GQA causal
        (1, 8, 8, 128, 128, 32, True, 96),        # sliding window
        (2, 2, 1, 64, 192, 64, False, None),      # cross-ish, MQA
        (1, 4, 4, 256, 256, 128, True, None),     # MXU-aligned d
        (1, 4, 2, 160, 160, 64, True, None),      # uneven tail (pad+mask)
        (1, 2, 2, 197, 197, 32, True, 64),        # prime len + window
    ])
def test_flash_attention(dtype, b, hq, hkv, sq, skv, d, causal, window):
    ks = jax.random.split(KEY, 3)
    q = _rand((b, hq, sq, d), dtype, ks[0])
    k = _rand((b, hkv, skv, d), dtype, ks[1])
    v = _rand((b, hkv, skv, d), dtype, ks[2])
    off = skv - sq
    got, lse = ops.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=off, block_q=64, block_k=64,
                                   return_lse=True, interpret=True)
    want, lse_want = ref.flash_attention_ref(q, k, v, causal=causal,
                                             window=window, q_offset=off,
                                             return_lse=True)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < tol
    assert float(jnp.max(jnp.abs(lse - lse_want))) < tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,window",
    [
        (2, 4, 2, 128, 128, 64, True, None),      # GQA causal
        (1, 8, 8, 128, 128, 32, True, 96),        # sliding window
        (2, 2, 1, 64, 192, 64, False, None),      # cross-ish, MQA
        (1, 4, 2, 160, 160, 64, True, None),      # uneven tail (pad+mask)
        (1, 2, 2, 197, 197, 32, True, 64),        # prime len + window
    ])
def test_flash_attention_grad(dtype, b, hq, hkv, sq, skv, d, causal,
                              window):
    """The Pallas backward kernels (preprocess/dKV/dQ) vs jax.vjp over
    the O(S^2) reference, across mask x GQA x dtype x uneven tails."""
    ks = jax.random.split(KEY, 4)
    q = _rand((b, hq, sq, d), dtype, ks[0])
    k = _rand((b, hkv, skv, d), dtype, ks[1])
    v = _rand((b, hkv, skv, d), dtype, ks[2])
    g = _rand((b, hq, sq, d), dtype, ks[3])
    off = skv - sq

    _, vjp_kernel = jax.vjp(
        lambda q_, k_, v_: ops.flash_attention_ad(
            q_, k_, v_, None, causal, window, off, block_q=64, block_k=64,
            interpret=True), q, k, v)
    _, vjp_ref = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, q_offset=off),
        q, k, v)
    for name, got, want in zip("qkv", vjp_kernel(g), vjp_ref(g)):
        want = want.astype(jnp.float32)
        tol = (1e-5 if dtype == jnp.float32 else 5e-2) \
            * max(1.0, float(jnp.max(jnp.abs(want))))
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        assert err < tol, (name, err, tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,nh,s,dh,chunk", [
    (2, 3, 128, 32, 32),
    (1, 2, 64, 64, 16),
    (1, 1, 96, 16, 96),   # single chunk
])
def test_mlstm_chunked(dtype, b, nh, s, dh, chunk):
    ks = jax.random.split(KEY, 5)
    q = _rand((b, nh, s, dh), dtype, ks[0])
    k = (_rand((b, nh, s, dh), dtype, ks[1]).astype(jnp.float32)
         * dh ** -0.5).astype(dtype)
    v = _rand((b, nh, s, dh), dtype, ks[2])
    ig = _rand((b, nh, s), jnp.float32, ks[3])
    lf = -jax.nn.softplus(-_rand((b, nh, s), jnp.float32, ks[4]) - 2.0)
    h_got, (C1, n1, m1) = ops.mlstm_chunked(q, k, v, ig, lf, chunk=chunk,
                                            interpret=True)
    h_ref, (C2, n2, m2) = ref.mlstm_chunked_ref(q, k, v, ig, lf)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    assert float(jnp.max(jnp.abs(h_got.astype(jnp.float32)
                                 - h_ref.astype(jnp.float32)))) < tol
    assert float(jnp.max(jnp.abs(C1 - C2))) < tol
    assert float(jnp.max(jnp.abs(m1 - m2))) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,r,scale", [
    (128, 256, 192, 8, 0.5),
    (64, 512, 64, 16, 2.0),
    (256, 128, 128, 4, 1.0),
])
def test_lora_matmul(dtype, m, k, n, r, scale):
    ks = jax.random.split(KEY, 4)
    x = _rand((m, k), dtype, ks[0])
    w = _rand((k, n), dtype, ks[1])
    a = _rand((k, r), dtype, ks[2])
    b = _rand((r, n), dtype, ks[3])
    got = ops.lora_matmul(x, w, a, b, scale=scale, block_m=64, block_n=64,
                          block_k=64, interpret=True)
    want = ref.lora_matmul_ref(x, w, a, b, scale=scale)
    tol = 1e-3 if dtype == jnp.float32 else 0.25
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,r,scale", [
    (128, 256, 192, 8, 0.5),
    (100, 96, 132, 4, 1.0),    # dims not multiples of the tile
    (300, 384, 136, 4, 2.0),   # no legal tile divides M or N: padded
])
def test_lora_matmul_grad(dtype, m, k, n, r, scale):
    """lora_matmul_ad's closed-form VJP vs jax.vjp over the oracle (the
    raw pallas_call has no autodiff rule at all)."""
    ks = jax.random.split(KEY, 5)
    x = _rand((m, k), dtype, ks[0])
    w = _rand((k, n), dtype, ks[1])
    a = _rand((k, r), dtype, ks[2])
    b = _rand((r, n), dtype, ks[3])
    g = _rand((m, n), dtype, ks[4])
    out, vjp_kernel = jax.vjp(
        lambda *t: ops.lora_matmul_ad(*t, scale=scale, block_m=64,
                                      block_n=64, block_k=64,
                                      interpret=True), x, w, a, b)
    out_ref, vjp_ref = jax.vjp(
        lambda *t: ref.lora_matmul_ref(*t, scale=scale), x, w, a, b)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - out_ref.astype(jnp.float32)))) \
        < (1e-3 if dtype == jnp.float32 else 0.25)
    for name, got, want in zip(["dx", "dw", "da", "db"],
                               vjp_kernel(g), vjp_ref(g)):
        want = want.astype(jnp.float32)
        tol = (1e-4 if dtype == jnp.float32 else 5e-2) \
            * max(1.0, float(jnp.max(jnp.abs(want))))
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        assert err < tol, (name, err, tol)


def test_flash_attention_matches_model_attention():
    """The kernel agrees with the model's chunked XLA path."""
    from repro.models import blocks as B
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 64))
    k = jax.random.normal(ks[1], (2, 2, 128, 64))
    v = jax.random.normal(ks[2], (2, 2, 128, 64))
    pos = jnp.arange(128)
    xla = B.chunked_mha(q, k, v, scale=64 ** -0.5, q_pos=pos, kv_pos=pos,
                        causal=True, window=None, kv_chunk=64)
    pall = ops.flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=64, interpret=True)
    assert float(jnp.max(jnp.abs(xla - pall))) < 5e-5


@pytest.mark.parametrize("m,block_rows", [(8, 256), (520, 256), (96, 32),
                                          (300, 256), (41, 40)])
def test_quantize_int8_matches_ref(m, block_rows):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (m, 128), jnp.float32) * 3.0
    x = x.at[min(3, m - 1)].set(0.0)                 # all-zero row
    bits = jax.random.bits(ks[1], (m, 128), jnp.uint32)
    q, s = ops.quantize_int8(x, bits, block_rows=block_rows,
                             interpret=True)
    q_ref, s_ref = ref.quantize_int8_ref(x, bits)
    assert q.dtype == jnp.int8 and s.shape == (m, 1)
    assert jnp.array_equal(q, q_ref)
    assert jnp.allclose(s, s_ref)
    got = ops.dequantize_int8(q, s, block_rows=block_rows, interpret=True)
    want = ref.dequantize_int8_ref(q_ref, s_ref)
    assert jnp.allclose(got, want)


def test_uniform24_is_exact_and_below_one():
    """The kernel's u = top 24 bits * 2**-24 (no uint32 -> float32 cast
    on the chip): exact in float32, in [0, 1), and 2**31 -> 0.5 exactly,
    the round-to-nearest word the int8 KV cache pins."""
    from repro.kernels.quantize import uniform24
    from repro.serve.kvcache import NEAREST_BITS
    bits = jnp.asarray([0, 255, 256, 1 << 31, (1 << 32) - 1], jnp.uint32)
    u = uniform24(bits)
    want = jnp.asarray([0.0, 0.0, 2.0 ** -24, 0.5, 1.0 - 2.0 ** -24])
    assert jnp.array_equal(u, want)
    assert float(uniform24(NEAREST_BITS)) == 0.5
    rand = jax.random.bits(KEY, (64, 128), jnp.uint32)
    assert jnp.array_equal(uniform24(rand),
                           (rand >> 8).astype(jnp.float32) * 2.0 ** -24)


def test_quantize_int8_nearest_bits_round_to_nearest():
    """With every bit word pinned to NEAREST_BITS the kernel rounds
    floor(x / scale + 0.5) — the int8 KV cache's deterministic contract."""
    from repro.serve.kvcache import NEAREST_BITS
    x = jax.random.normal(KEY, (40, 128), jnp.float32)
    bits = jnp.full(x.shape, NEAREST_BITS, jnp.uint32)
    q, s = ops.quantize_int8(x, bits, interpret=True)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    want = jnp.clip(jnp.floor(x / scale + 0.5), -127, 127).astype(jnp.int8)
    assert jnp.array_equal(q, want)


def test_auto_interpret_follows_the_backend(monkeypatch):
    """Mosaic on a TPU, interpret on the CPU, and an error anywhere
    else: no backend silently falls back to interpreted kernels."""
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._auto_interpret(None) is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._auto_interpret(None)
    assert ops._auto_interpret(True) is True      # explicit choice wins
    assert ops._auto_interpret(False) is False


def test_quantize_int8_error_bound_and_zero_rows():
    """Round-trip error < one quantization step per row; zero rows stay
    exactly zero (scale 0 on the wire, not NaN)."""
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (64, 128), jnp.float32) * 10.0
    x = x.at[5].set(0.0)
    bits = jax.random.bits(ks[1], (64, 128), jnp.uint32)
    q, s = ops.quantize_int8(x, bits, interpret=True)
    back = ops.dequantize_int8(q, s, interpret=True)
    step = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    assert float(jnp.max(jnp.abs(back - x) - step)) <= 1e-6
    assert float(jnp.abs(back[5]).max()) == 0.0
    assert float(s[5, 0]) == 0.0


def test_quantize_int8_stochastic_rounding_unbiased():
    """E[dequant(quant(x))] -> x: averaging round-trips over many draws
    shrinks the error well below a single deterministic rounding step."""
    x = jnp.full((8, 128), 0.3456789, jnp.float32)
    x = x.at[:, 0].set(5.0)                  # pins scale = 5/127
    acc = jnp.zeros_like(x)
    n = 64
    for i in range(n):
        bits = jax.random.bits(jax.random.PRNGKey(i), (8, 128),
                               jnp.uint32)
        q, s = ops.quantize_int8(x, bits, interpret=True)
        acc = acc + ops.dequantize_int8(q, s, interpret=True)
    mean_err = float(jnp.abs(acc / n - x)[:, 1:].max())
    step = 5.0 / 127.0
    assert mean_err < 0.25 * step, (mean_err, step)


# The kernels read page-major layer stacks [L, NB, bs, Hkv * D] at a
# layer index; the tests' pools hold two layers and read the second.
LAYERS, LAYER = 2, 1


def _paged_setup(k, b, hkv, nb, bs, d, ctx_list, t=None):
    """Random pools [LAYERS, NB, bs, Hkv * D] + a valid block table for the
    given context lengths (``t`` table slots; one more than the longest
    context needs)."""
    import numpy as np
    ks = jax.random.split(k, 3)
    kp = jax.random.normal(ks[0], (LAYERS, nb, bs, hkv * d), jnp.float32)
    vp = jax.random.normal(ks[1], (LAYERS, nb, bs, hkv * d), jnp.float32)
    t = t or max(-(-c // bs) for c in ctx_list) + 1
    tbl = np.zeros((b, t), np.int32)
    free = list(range(1, nb))
    for i, c in enumerate(ctx_list):
        for j in range(-(-c // bs)):
            tbl[i, j] = free.pop()
    return kp, vp, jnp.asarray(tbl), jnp.asarray(ctx_list, jnp.int32)


# The kernel walks compute blocks of 128 // bs pages (at most T), so
# bs 32 gives 4-page blocks and bs 64 2-page blocks at test sizes.
@pytest.mark.parametrize(
    "b,hq,hkv,d,bs,ctx_list,t",
    [
        (4, 4, 2, 32, 8, [13, 1, 0, 48], None),  # GQA, partial/dead/full
        (2, 8, 8, 64, 16, [16, 31], None),       # MHA, exact, off-by-one
        (3, 2, 1, 128, 4, [4, 9, 2], None),      # MQA, tiny blocks
        # 3 and 2 compute blocks, ragged last blocks; T 11 is not a
        # multiple of the 4-page block
        (2, 4, 2, 32, 32, [300, 129], None),
        (2, 2, 1, 16, 64, [320, 0], 5),          # a lane at the full table
        (3, 4, 2, 32, 16, [0, 0, 0], None),      # every lane dead
    ])
def test_paged_decode_attention(b, hq, hkv, d, bs, ctx_list, t):
    """Paged single-token decode kernel vs the dense gather oracle,
    including dead lanes (ctx=0 -> exact zeros) and partial last blocks."""
    nb = 1 + sum(-(-c // bs) for c in ctx_list) + 2
    kp, vp, tbl, ctx = _paged_setup(KEY, b, hkv, nb, bs, d, ctx_list, t)
    q = jax.random.normal(jax.random.fold_in(KEY, 7), (b, hq, d),
                          jnp.float32)
    got = ops.paged_decode_attention(q, kp, vp, tbl, ctx, layer=LAYER,
                                     interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, tbl, ctx, layer=LAYER)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    for i, c in enumerate(ctx_list):
        if c == 0:
            assert float(jnp.abs(got[i]).max()) == 0.0


def _int8_scales(key, pool, hkv):
    """Random float32 absmax scales for an int8 pool: one row of bs * Hkv
    a page, [L, NB, 1, bs * Hkv]."""
    l, nb, bs, _ = pool.shape
    return jax.random.uniform(key, (l, nb, 1, bs * hkv), jnp.float32, 1e-3,
                              2e-2)


@pytest.mark.parametrize("bs,ctx_list", [
    (8, [5, 17, 24]),
    (32, [300, 0, 97]),            # several compute blocks, ragged last
])
def test_paged_decode_attention_int8(bs, ctx_list):
    """int8 pools dequantize in-kernel through per-row scales."""
    b, hq, hkv, d = 3, 4, 2, 32
    nb = 1 + sum(-(-c // bs) for c in ctx_list) + 1
    kp, vp, tbl, ctx = _paged_setup(KEY, b, hkv, nb, bs, d, ctx_list)
    ks = jax.random.split(jax.random.fold_in(KEY, 11), 5)
    kq = jax.random.randint(ks[0], kp.shape, -127, 128,
                            jnp.int32).astype(jnp.int8)
    vq = jax.random.randint(ks[1], vp.shape, -127, 128,
                            jnp.int32).astype(jnp.int8)
    ksc = _int8_scales(ks[2], kp, hkv)
    vsc = _int8_scales(ks[3], vp, hkv)
    q = jax.random.normal(ks[4], (b, hq, d), jnp.float32)
    got = ops.paged_decode_attention(q, kq, vq, tbl, ctx, layer=LAYER,
                                     k_scales=ksc, v_scales=vsc,
                                     interpret=True)
    want = ref.paged_decode_attention_ref(q, kq, vq, tbl, ctx, layer=LAYER,
                                          k_scales=ksc, v_scales=vsc)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


def _poison_dead(pool, tbl, ctx_list, bs, hkv=1):
    """NaN in every pool row outside the lanes' live ranges: every block
    no lane's context reaches (the null block 0 included) and the rows
    past the context in each lane's partial last page. A scale pool [L,
    NB, 1, bs * Hkv] is poisoned at the lanes of those tokens."""
    import numpy as np
    live = np.zeros((pool.shape[1], bs), bool)        # [NB, bs]
    for row, c in zip(np.asarray(tbl), ctx_list):
        for j in range(-(-c // bs)):
            live[row[j], :min(bs, c - j * bs)] = True
    if pool.shape[2] == 1 and pool.shape[3] == bs * hkv:
        live = np.repeat(live, hkv, axis=1)[:, None, :]  # lane t*Hkv + h
    else:
        live = live[:, :, None]
    return jnp.where(jnp.asarray(live)[None], pool, jnp.nan)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_paged_decode_never_reads_dead_pages(int8):
    """With NaN wherever no lane's context reaches, the output is finite
    and matches the oracle on clean pools: the kernel never computes
    with a dead page, a dead lane's null block or the tail of a
    partial page."""
    b, hq, hkv, d, bs = 4, 4, 2, 32, 32
    ctx_list = [300, 0, 129, 31]          # multi-block, dead, ragged, short
    nb = 1 + sum(-(-c // bs) for c in ctx_list) + 3
    kp, vp, tbl, ctx = _paged_setup(KEY, b, hkv, nb, bs, d, ctx_list)
    q = jax.random.normal(jax.random.fold_in(KEY, 13), (b, hq, d),
                          jnp.float32)
    if int8:
        ks = jax.random.split(jax.random.fold_in(KEY, 17), 2)
        kp = jnp.clip(jnp.round(kp * 40), -127, 127).astype(jnp.int8)
        vp = jnp.clip(jnp.round(vp * 40), -127, 127).astype(jnp.int8)
        scales = [_int8_scales(k, kp, hkv) for k in ks]
        want = ref.paged_decode_attention_ref(
            q, kp, vp, tbl, ctx, layer=LAYER, k_scales=scales[0],
            v_scales=scales[1])
        ksc, vsc = (_poison_dead(s_, tbl, ctx_list, bs, hkv)
                    for s_ in scales)
        got = ops.paged_decode_attention(q, kp, vp, tbl, ctx, layer=LAYER,
                                         k_scales=ksc, v_scales=vsc,
                                         interpret=True)
    else:
        want = ref.paged_decode_attention_ref(q, kp, vp, tbl, ctx,
                                              layer=LAYER)
        got = ops.paged_decode_attention(
            q, _poison_dead(kp, tbl, ctx_list, bs),
            _poison_dead(vp, tbl, ctx_list, bs), tbl, ctx, layer=LAYER,
            interpret=True)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    assert float(jnp.abs(got[1]).max()) == 0.0


def _pages(x, bs):
    """A contiguous stream [Hkv, S, D] as page-major pages [S / bs, bs,
    Hkv * D] (zero-padded to whole pages)."""
    hkv, s, d = x.shape
    t = -(-s // bs)
    x = jnp.pad(x, ((0, 0), (0, t * bs - s), (0, 0)))
    return x.transpose(1, 0, 2).reshape(t, bs, hkv * d)


def _quantize_pool(pool, hkv):
    """int8 codes and [L, NB, 1, bs * Hkv] scales of a page-major pool,
    quantized per (token, head) row as the engine writes them."""
    from repro.serve.kvcache import quantize_rows
    l, nb, bs, w = pool.shape
    q, sc = quantize_rows(pool.reshape(l, nb, bs, hkv, w // hkv))
    return q.reshape(pool.shape), sc.reshape(l, nb, 1, bs * hkv)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_paged_kernels_read_only_their_layer(kernel, int8):
    """Handed the whole layer stacks, a kernel reads the layer it is told
    and no other: NaN in every other layer leaves its output finite and
    equal to the oracle's on clean stacks."""
    b, hq, hkv, d, bs, ctx_list = 3, 4, 2, 64, 16, [40, 0, 17]
    nb = 1 + sum(-(-c // bs) for c in ctx_list) + 1
    kp, vp, tbl, ctx = _paged_setup(KEY, b, hkv, nb, bs, d, ctx_list)
    kw = {}
    if int8:
        ks = jax.random.split(jax.random.fold_in(KEY, 61), 2)
        kp = jnp.clip(jnp.round(kp * 40), -127, 127).astype(jnp.int8)
        vp = jnp.clip(jnp.round(vp * 40), -127, 127).astype(jnp.int8)
        kw = dict(k_scales=_int8_scales(ks[0], kp, hkv),
                  v_scales=_int8_scales(ks[1], vp, hkv))
    other = jnp.arange(LAYERS)[:, None, None, None] != LAYER
    if kernel == "decode":
        q = jax.random.normal(jax.random.fold_in(KEY, 67), (b, hq, d))
        want = ref.paged_decode_attention_ref(q, kp, vp, tbl, ctx,
                                              layer=LAYER, **kw)
        run = functools.partial(ops.paged_decode_attention, q,
                                block_tables=tbl, ctx_lens=ctx)
    else:
        q = jax.random.normal(jax.random.fold_in(KEY, 71), (hq, 8, d))
        want = ref.paged_prefill_attention_ref(q, kp, vp, tbl[0], 32, 40,
                                               layer=LAYER, **kw)[:, :8]
        run = functools.partial(ops.paged_prefill_attention, q,
                                block_table=tbl[0], q_offset=32, ctx_len=40)
    if not int8:        # NaN in the other layer's K and V
        poisoned = {n: jnp.where(other, jnp.nan, x) for n, x in
                    dict(kp=kp, vp=vp).items()}
    else:               # int8 codes cannot hold NaN: poison the scales
        poisoned = dict(kp=kp, vp=vp, **{n: jnp.where(other, jnp.nan, x)
                                         for n, x in kw.items()})
    got = run(k_pages=poisoned.pop("kp"), v_pages=poisoned.pop("vp"),
              layer=LAYER, interpret=True, **poisoned)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


def _prefill_pool_setup(key, hkv, bs, d, s, spare=2, int8=False):
    """A contiguous K/V stream scattered into shuffled physical blocks of
    layer ``LAYER`` of the stacks (the other layer random), plus the
    block table that maps it back (trailing entries null)."""
    import numpy as np
    t = -(-s // bs)
    nb = 1 + t + spare
    ks = jax.random.split(key, 5)
    k = jax.random.normal(ks[0], (hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[1], (hkv, s, d), jnp.float32)
    rng = np.random.default_rng(int(jax.random.randint(ks[2], (), 0, 1 << 30)))
    phys = rng.permutation(np.arange(1, nb))[:t]
    other = jax.random.normal(ks[3], (LAYERS, nb, bs, hkv * d), jnp.float32)
    kp = other.at[LAYER].set(0.0).at[LAYER, phys].set(_pages(k, bs))
    vp = (-other).at[LAYER].set(0.0).at[LAYER, phys].set(_pages(v, bs))
    tbl = np.zeros(t + 1, np.int32)
    tbl[:t] = phys
    scales = None
    if int8:
        kp, ksc = _quantize_pool(kp, hkv)
        vp, vsc = _quantize_pool(vp, hkv)
        scales = (ksc, vsc)
    return k, v, kp, vp, jnp.asarray(tbl), scales


@pytest.mark.parametrize(
    "hq,hkv,d,bs,chunk,ctx,off",
    [
        (4, 2, 32, 8, 8, 21, 0),      # GQA, first chunk
        (4, 2, 32, 8, 8, 21, 8),      # mid chunk over earlier blocks
        (4, 2, 32, 8, 8, 21, 16),     # final partial chunk (5 live rows)
        (8, 8, 64, 16, 16, 16, 0),    # MHA, one exact-fit chunk
        (2, 1, 128, 4, 4, 9, 4),      # MQA, tiny blocks, odd tail
        (4, 2, 32, 8, 16, 37, 16),    # chunk spanning multiple blocks
        # lane groups: 2 heads of 64 per 128-lane slice, 2 groups of 2
        # query heads each; 1 head of 128 per slice, 2 groups of 4
        (8, 4, 64, 8, 8, 21, 8),
        (8, 2, 128, 8, 8, 21, 8),
    ])
def test_paged_prefill_attention(hq, hkv, d, bs, chunk, ctx, off):
    """Chunked paged prefill kernel vs the dense gather oracle: a C-row
    query chunk at q_offset attends causally through the block table."""
    _, _, kp, vp, tbl, _ = _prefill_pool_setup(jax.random.fold_in(KEY, 13),
                                               hkv, bs, d, ctx)
    q = jax.random.normal(jax.random.fold_in(KEY, 17), (hq, chunk, d),
                          jnp.float32)
    got = ops.paged_prefill_attention(q, kp, vp, tbl, off, ctx, layer=LAYER,
                                      interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, vp, tbl, off, ctx,
                                           layer=LAYER)
    clen = ctx - off            # rows past the live chunk are garbage
    assert got.shape == (hq, chunk, d)
    err = float(jnp.max(jnp.abs(got[:, :clen] - want[:, :clen])))
    assert err < 5e-6


# one group of the whole row; two lane groups of 2 heads of 64
@pytest.mark.parametrize("hq,hkv,d", [(4, 2, 32), (8, 4, 64)])
def test_paged_prefill_attention_int8(hq, hkv, d):
    """int8 pools dequantize in-kernel through per-row scales."""
    bs, chunk, ctx, off = 8, 8, 19, 8
    _, _, kp, vp, tbl, (ksc, vsc) = _prefill_pool_setup(
        jax.random.fold_in(KEY, 19), hkv, bs, d, ctx, int8=True)
    q = jax.random.normal(jax.random.fold_in(KEY, 23), (hq, chunk, d),
                          jnp.float32)
    got = ops.paged_prefill_attention(q, kp, vp, tbl, off, ctx, layer=LAYER,
                                      k_scales=ksc, v_scales=vsc,
                                      interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, vp, tbl, off, ctx,
                                           layer=LAYER, k_scales=ksc,
                                           v_scales=vsc)
    clen = ctx - off
    assert float(jnp.max(jnp.abs(got[:, :clen] - want[:, :clen]))) < 5e-6


def test_paged_prefill_dead_blocks_skipped():
    """Table entries beyond the context are never read: pointing them at
    a NaN-poisoned block must not change the output (the kernel's
    dead-block skip, not masking, is what protects the accumulator)."""
    import numpy as np
    hq, hkv, d, bs = 4, 2, 32, 8
    ctx, off = 12, 8                     # 2 live blocks, chunk rows 8..11
    _, _, kp, vp, tbl, _ = _prefill_pool_setup(jax.random.fold_in(KEY, 29),
                                               hkv, bs, d, ctx, spare=2)
    q = jax.random.normal(jax.random.fold_in(KEY, 31), (hq, bs, d),
                          jnp.float32)
    live = -(-ctx // bs)
    poison = int(max(np.asarray(tbl))) + 1      # a spare, unused block
    kp = kp.at[:, poison].set(jnp.nan)
    vp = vp.at[:, poison].set(jnp.nan)
    tbl_nan = np.asarray(tbl).copy()
    tbl_nan[live:] = poison
    got = ops.paged_prefill_attention(q, kp, vp, jnp.asarray(tbl_nan),
                                      off, ctx, layer=LAYER, interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, vp, tbl, off, ctx,
                                           layer=LAYER)
    clen = ctx - off
    assert bool(jnp.isfinite(got[:, :clen]).all())
    assert float(jnp.max(jnp.abs(got[:, :clen] - want[:, :clen]))) < 5e-6


def test_paged_prefill_chunks_match_flash():
    """A full causal prefill assembled from sequential fixed-size chunks
    reproduces the dense flash oracle on the contiguous stream."""
    hq, hkv, d, bs, s, chunk = 4, 2, 32, 8, 21, 8
    k, v, kp, vp, tbl, _ = _prefill_pool_setup(jax.random.fold_in(KEY, 37),
                                               hkv, bs, d, s)
    q = jax.random.normal(jax.random.fold_in(KEY, 41), (hq, s, d),
                          jnp.float32)
    outs = []
    for off in range(0, s, chunk):
        clen = min(chunk, s - off)
        qc = jnp.zeros((hq, chunk, d)).at[:, :clen].set(
            q[:, off:off + clen])
        o = ops.paged_prefill_attention(qc, kp, vp, tbl, off, off + clen,
                                        layer=LAYER, interpret=True)
        outs.append(o[:, :clen])
    got = jnp.concatenate(outs, axis=1)
    want = ref.flash_attention_ref(q[None], k[None], v[None],
                                   causal=True)[0]
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


def test_paged_decode_matches_contiguous_attention():
    """Scattering a contiguous K/V stream into shuffled physical blocks
    must not change attention output vs the flash kernel on the same
    stream (single query at the last position)."""
    import numpy as np
    b, hq, hkv, d, bs, s = 2, 4, 2, 32, 8, 21
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    dense = ref.flash_attention_ref(q, k, v, causal=True, q_offset=s - 1)

    t = -(-s // bs)
    nb = 1 + b * t
    rng = np.random.default_rng(3)
    phys = rng.permutation(np.arange(1, nb)).reshape(b, t)
    kp = jnp.zeros((1, nb, bs, hkv * d), jnp.float32)
    vp = jnp.zeros((1, nb, bs, hkv * d), jnp.float32)
    for i in range(b):
        kp = kp.at[0, phys[i]].set(_pages(k[i], bs))
        vp = vp.at[0, phys[i]].set(_pages(v[i], bs))
    ctx = jnp.full((b,), s, jnp.int32)
    got = ops.paged_decode_attention(q[:, :, 0], kp, vp,
                                     jnp.asarray(phys, jnp.int32), ctx,
                                     layer=0, interpret=True)
    assert float(jnp.max(jnp.abs(got - dense[:, :, 0]))) < 5e-6


# ------------------------------------------- latent (MLA) paged kernels ---
def _latent_values(pool, dv):
    """The latent pool as the oracle's V pool: a row's first ``dv`` lanes,
    the rest zero (the oracle's output keeps them zero)."""
    return jnp.where(jnp.arange(pool.shape[-1]) < dv, pool, 0.0)


# a latent row of 40 (32 + 8) padded to 128 lanes; Moonlight's 576 in 640
@pytest.mark.parametrize("b,hq,d,dv,bs,ctx_list", [
    (4, 4, 128, 32, 8, [13, 1, 0, 48]),
    (3, 16, 640, 512, 16, [40, 0, 300]),
])
def test_paged_decode_latent(b, hq, d, dv, bs, ctx_list):
    """One latent pool is keys and (its first ``dv`` lanes) values: the
    kernel matches the gather oracle on those pools, every dead page and
    dead lane poisoned with NaN, dead lanes giving zeros."""
    nb = 1 + sum(-(-c // bs) for c in ctx_list) + 2
    kp, _, tbl, ctx = _paged_setup(KEY, b, 1, nb, bs, d, ctx_list)
    q = jax.random.normal(jax.random.fold_in(KEY, 43), (b, hq, d),
                          jnp.float32)
    scale = 192 ** -0.5
    want = ref.paged_decode_attention_ref(q, kp, _latent_values(kp, dv), tbl,
                                          ctx, layer=LAYER,
                                          scale=scale)[..., :dv]
    got = ops.paged_decode_attention(
        q, _poison_dead(kp, tbl, ctx_list, bs), None, tbl, ctx, layer=LAYER,
        scale=scale, latent_v=dv, interpret=True)
    assert got.shape == (b, hq, dv)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    for i, c in enumerate(ctx_list):
        if c == 0:
            assert float(jnp.abs(got[i]).max()) == 0.0


@pytest.mark.parametrize("hq,d,dv,bs,chunk,ctx,off", [
    (4, 128, 32, 8, 8, 21, 8),
    (16, 640, 512, 16, 32, 70, 64),
])
def test_paged_prefill_latent(hq, d, dv, bs, chunk, ctx, off):
    """The chunked-prefill kernel over one latent pool (keys, and values
    in the first ``dv`` lanes) matches the gather oracle."""
    _, _, kp, _, tbl, _ = _prefill_pool_setup(jax.random.fold_in(KEY, 47),
                                              1, bs, d, ctx)
    q = jax.random.normal(jax.random.fold_in(KEY, 53), (hq, chunk, d),
                          jnp.float32)
    got = ops.paged_prefill_attention(q, kp, None, tbl, off, ctx,
                                      layer=LAYER, scale=192 ** -0.5,
                                      latent_v=dv, interpret=True)
    want = ref.paged_prefill_attention_ref(q, kp, _latent_values(kp, dv),
                                           tbl, off, ctx, layer=LAYER,
                                           scale=192 ** -0.5)[..., :dv]
    clen = ctx - off
    assert got.shape == (hq, chunk, dv)
    assert float(jnp.max(jnp.abs(got[:, :clen] - want[:, :clen]))) < 5e-6


def test_latent_kernels_refuse_mixed_pools():
    q = jnp.zeros((2, 4, 128))
    kp = jnp.zeros((1, 4, 8, 128))
    tbl, ctx = jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="latent"):
        ops.paged_decode_attention(q, kp, kp, tbl, ctx, layer=0,
                                   latent_v=64, interpret=True)
    with pytest.raises(ValueError, match="latent"):
        ops.paged_decode_attention(q, kp, None, tbl, ctx, layer=0,
                                   interpret=True)


# --------------------------------------------- grouped expert SwiGLU ------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes", [[5, 0, 17, 1], [0, 0, 0, 0],
                                   [40, 0, 0, 0]],
                         ids=["ragged", "empty", "one-expert"])
@pytest.mark.parametrize("f", [48, 384], ids=["whole", "3-blocks"])
def test_moe_expert_ffn(dtype, sizes, f, monkeypatch):
    """Rows sorted by expert and padded to whole tiles: each row gets its
    own expert's SwiGLU, tiles past the live ones are zero, and nothing
    depends on the padding rows; an expert wider than the VMEM allowed
    for its weights is summed over blocks of the width."""
    import numpy as np
    from repro.kernels import moe
    from repro.kernels.moe import moe_expert_ffn_ref
    tile, d, e = 16, 32, len(sizes)
    # room for the weights of 128 of the 384 lanes, double-buffered
    monkeypatch.setattr(moe, "WEIGHT_VMEM_BYTES", 2 * 3 * d * 128 * 4)
    assert moe.block_f(d, f, 4) == min(f, 128)
    tiles = [-(-n // tile) for n in sizes]
    n_live = sum(tiles)
    n_tiles = n_live + 3                           # dead tiles after
    ks = jax.random.split(jax.random.fold_in(KEY, 59), 4)
    x = jax.random.normal(ks[0], (n_tiles * tile, d), jnp.float32)
    wi = jax.random.normal(ks[1], (e, d, f)) * d ** -0.5
    wg = jax.random.normal(ks[2], (e, d, f)) * d ** -0.5
    wo = jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
    x, wi, wg, wo = (a.astype(dtype) for a in (x, wi, wg, wo))
    te = np.concatenate([np.full(t, i) for i, t in enumerate(tiles)]
                        + [np.full(3, e - 1)]).astype(np.int32)
    n = jnp.array([n_live], jnp.int32)
    # the weights as layer stacks: two layers, the second one runs
    lay = jnp.array([1], jnp.int32)
    wi, wg, wo = (jnp.stack([jnp.flip(w, 0), w]) for w in (wi, wg, wo))
    got = ops.moe_expert_ffn(x, jnp.asarray(te), n, lay, wi, wg, wo,
                             tile=tile, interpret=True)
    want = moe_expert_ffn_ref(x, jnp.asarray(te), n, lay, wi, wg, wo,
                              tile=tile)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < tol
    assert float(jnp.abs(got[n_live * tile:].astype(jnp.float32)).max()) == 0
    if dtype == jnp.float32:                       # one row by hand
        i = 0 if sizes[0] else None
        if i is not None:
            h = jax.nn.silu(x[i] @ wg[1, 0]) * (x[i] @ wi[1, 0])
            assert float(jnp.abs(got[i] - h @ wo[1, 0]).max()) < 1e-5


def test_moe_expert_ffn_block_width():
    """The whole expert where its weights fit; else the widest multiple
    of 128 lanes dividing its width; else a clear refusal."""
    from repro.kernels.moe import block_f
    assert block_f(2048, 1408, 2) == 1408          # Moonlight
    assert block_f(2048, 768, 2) == 768            # Qwen3-MoE
    assert block_f(6144, 10752, 2) == 512          # DBRX
    with pytest.raises(ValueError, match="VMEM"):
        block_f(2 ** 20, 10752, 2)
