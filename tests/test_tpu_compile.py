"""The main-path Pallas kernels compile for a TPU v5e at full flad-adllm
widths (Hq=16, Hkv=8, head_dim 64, d_model 1024, bf16), and the latent
(MLA) and held-expert kernels at moonlight-serve-plans' (64 lanes, 16
heads over a 512 + 64 latent row in 640 lanes, 16 held experts of
2048 x 1408).

Nothing runs: each test lowers a kernel for a described (not attached)
v5e chip and compiles it with the TPU compiler, which refuses what
interpret mode accepts (block shapes off the (8, 128) tiling, casts
Mosaic lacks). The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library.

The serving engine's decode and prefill-chunk programs are compiled
whole, for a K/V and a latent pool, to show that they write the donated
pools in place: every pool is aliased to an output and nothing but the
in-place scatter of new rows yields a pool-sized result.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HQ, HKV, D, DMODEL = 16, 8, 64, 1024
BF16 = jnp.bfloat16
SLOTS, BS, T = 8, 16, 35              # 8 lanes, 16-token blocks, 560 tokens
NB = 1 + SLOTS * T + 1                 # null block + lanes + headroom
LAYERS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory on one described v5e chip; the persistent
    compilation cache stays off (its entries could not be read back
    without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _mosaic_calls(fn, *args) -> int:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    return hlo.count('custom_call_target="tpu_custom_call"')


def _qkv(sds, s=1024):
    return (sds((1, HQ, s, D), BF16), sds((1, HKV, s, D), BF16),
            sds((1, HKV, s, D), BF16))


def test_flash_forward_compiles(sds):
    fwd = functools.partial(ops.flash_attention, return_lse=True,
                            interpret=False)
    assert _mosaic_calls(fwd, *_qkv(sds)) == 1


def test_flash_backward_compiles(sds):
    def fwd_bwd(q, k, v, g):
        attn = functools.partial(ops.flash_attention_ad, interpret=False)
        _, vjp = jax.vjp(attn, q, k, v)
        return vjp(g)

    # forward + the preprocess, dK/dV and dQ kernels
    assert _mosaic_calls(fwd_bwd, *_qkv(sds),
                         sds((1, HQ, 1024, D), BF16)) == 4


# the fleet cell's geometry besides: 32 lanes, 144-slot tables over a
# 2,048-block pool (1 GiB of bf16 K/V across 16 layers)
@pytest.mark.parametrize(
    "int8,slots,t,nb",
    [(False, SLOTS, T, NB), (True, SLOTS, T, NB),
     (False, 32, 144, 2048), (True, 32, 144, 2048)],
    ids=["bf16", "int8", "bf16-fleet", "int8-fleet"])
def test_paged_decode_compiles(sds, int8, slots, t, nb):
    # the engine's page-major layer stacks, read at a traced layer
    pool = sds((LAYERS, nb, BS, HKV * D), jnp.int8 if int8 else BF16)
    scales = sds((LAYERS, nb, 1, BS * HKV), jnp.float32) if int8 else None

    def decode(q, kp, vp, tbl, ctx, layer, ks, vs):
        return ops.paged_decode_attention(q, kp, vp, tbl, ctx, layer=layer,
                                          k_scales=ks, v_scales=vs,
                                          interpret=False)

    assert _mosaic_calls(decode, sds((slots, HQ, D), BF16), pool, pool,
                         sds((slots, t), jnp.int32),
                         sds((slots,), jnp.int32), sds((), jnp.int32),
                         scales, scales) == 1


# the fleet cell's chunk of 32 tokens besides; and the widest GQA
# configurations served through the paged engine, qwen3-32b (64 query
# heads over 8 KV heads of 128) and dbrx (48 over 8 of 128), whose
# block-diagonal query over all eight KV heads would not fit VMEM
@pytest.mark.parametrize(
    "int8,chunk,hq,hkv,d",
    [(False, 16, HQ, HKV, D), (True, 16, HQ, HKV, D),
     (False, 32, HQ, HKV, D), (True, 32, HQ, HKV, D),
     (False, 32, 64, 8, 128), (True, 32, 64, 8, 128),
     (False, 32, 48, 8, 128)],
    ids=["bf16", "int8", "bf16-fleet", "int8-fleet", "bf16-qwen3-32b",
         "int8-qwen3-32b", "bf16-dbrx"])
def test_paged_prefill_compiles(sds, int8, chunk, hq, hkv, d):
    pool = sds((LAYERS, NB, BS, hkv * d), jnp.int8 if int8 else BF16)
    scales = sds((LAYERS, NB, 1, BS * hkv), jnp.float32) if int8 else None

    def prefill(q, kp, vp, tbl, off, ctx, layer, ks, vs):
        return ops.paged_prefill_attention(q, kp, vp, tbl, off, ctx,
                                           layer=layer, k_scales=ks,
                                           v_scales=vs, interpret=False)

    scalar = sds((), jnp.int32)
    assert _mosaic_calls(prefill, sds((hq, chunk, d), BF16), pool, pool,
                         sds((T,), jnp.int32), scalar, scalar, scalar,
                         scales, scales) == 1


@pytest.mark.parametrize("hq,hkv,d", [(64, 8, 128), (48, 8, 128)],
                         ids=["qwen3-32b", "dbrx"])
def test_paged_decode_compiles_at_wide_heads(sds, hq, hkv, d):
    """The decode kernel's block-diagonal query [Hq, Hkv * D] at the
    widest GQA configurations the engine serves."""
    pool = sds((LAYERS, NB, BS, hkv * d), BF16)

    def decode(q, kp, vp, tbl, ctx, layer):
        return ops.paged_decode_attention(q, kp, vp, tbl, ctx, layer=layer,
                                          interpret=False)

    assert _mosaic_calls(decode, sds((SLOTS, hq, d), BF16), pool, pool,
                         sds((SLOTS, T), jnp.int32),
                         sds((SLOTS,), jnp.int32), sds((), jnp.int32)) == 1


# 300 rows used to pick a 150-row tile, which is not a multiple of 8
@pytest.mark.parametrize("rows", [512, 300])
def test_quantize_roundtrip_compiles(sds, rows):
    def roundtrip(x, bits):
        q, scale = ops.quantize_int8(x, bits, interpret=False)
        return ops.dequantize_int8(q, scale, interpret=False)

    assert _mosaic_calls(roundtrip, sds((rows, 128), jnp.float32),
                         sds((rows, 128), jnp.uint32)) == 2


def test_lora_matmul_forward_and_dx_compile(sds):
    """wk at full width: x [1088, 1024] (8 x (128 tokens + 8 feature
    tokens)), w [1024, 512], rank 4; the forward and the closed-form dx
    both run the fused kernel."""
    def fwd_dx(x, w, a, b, g):
        f = functools.partial(ops.lora_matmul_ad, scale=2.0,
                              interpret=False)
        y, vjp = jax.vjp(lambda x_: f(x_, w, a, b), x)
        return y, vjp(g)

    m, n, r = 8 * 136, HKV * D, 4
    assert _mosaic_calls(fwd_dx, sds((m, DMODEL), BF16),
                         sds((DMODEL, n), BF16), sds((DMODEL, r), BF16),
                         sds((r, n), BF16), sds((m, n), BF16)) == 2


# moonlight-serve-plans: 64 lanes, 112-slot tables (1,792 tokens) over a
# 1 GiB latent pool of 13 layers (4,032 blocks of 16 rows of 640 lanes)
LAT_SLOTS, LAT_T, LAT_NB, LAT_D, LAT_V = 64, 112, 4032, 640, 512
LAT_LAYERS = 12


def test_latent_paged_decode_compiles(sds):
    def decode(q, pool, tbl, ctx, layer):
        return ops.paged_decode_attention(q, pool, None, tbl, ctx,
                                          layer=layer, scale=192 ** -0.5,
                                          latent_v=LAT_V, interpret=False)

    assert _mosaic_calls(decode, sds((LAT_SLOTS, HQ, LAT_D), BF16),
                         sds((LAT_LAYERS, LAT_NB, BS, LAT_D), BF16),
                         sds((LAT_SLOTS, LAT_T), jnp.int32),
                         sds((LAT_SLOTS,), jnp.int32),
                         sds((), jnp.int32)) == 1


def test_latent_paged_prefill_compiles(sds):
    def prefill(q, pool, tbl, off, ctx, layer):
        return ops.paged_prefill_attention(q, pool, None, tbl, off, ctx,
                                           layer=layer, scale=192 ** -0.5,
                                           latent_v=LAT_V, interpret=False)

    scalar = sds((), jnp.int32)
    assert _mosaic_calls(prefill, sds((HQ, 32, LAT_D), BF16),
                         sds((LAT_LAYERS, LAT_NB, BS, LAT_D), BF16),
                         sds((LAT_T,), jnp.int32), scalar, scalar,
                         scalar) == 1


@pytest.mark.parametrize("tokens,layers,held,top_k,d,f", [
    (64, 12, 16, 6, 2048, 1408), (32, 12, 16, 6, 2048, 1408),
    (64, 48, 32, 8, 2048, 768), (64, 2, 4, 4, 6144, 10752)],
    ids=["decode", "prefill", "qwen3-moe", "dbrx"])
def test_moe_expert_ffn_compiles(sds, tokens, layers, held, top_k, d, f):
    """The held experts' grouped SwiGLU: at the cell's widths (16 experts
    of 2048 x 1408 in the stacks of 12 expert layers), and one chip's
    share of Qwen3-MoE's (32 of 128 experts of 2048 x 768) and of DBRX's
    (4 of 16 experts of 6144 x 10752 in two layers, which fit the HBM;
    their weights are taken in blocks
    of the width); rows for ``tokens`` tokens x ``top_k`` choices padded
    to 16-row tiles (the bound ``blocks.held_moe`` sizes)."""
    def ffn(x, te, n, lay, wi, wg, wo):
        return ops.moe_expert_ffn(x, te, n, lay, wi, wg, wo, tile=16,
                                  interpret=False)

    rows = -(-(tokens * min(top_k, held) + held * 15) // 16) * 16
    assert _mosaic_calls(ffn, sds((rows, d), BF16),
                         sds((rows // 16,), jnp.int32), sds((1,), jnp.int32),
                         sds((1,), jnp.int32),
                         sds((layers, held, d, f), BF16),
                         sds((layers, held, d, f), BF16),
                         sds((layers, held, f, d), BF16)) == 1


# ------------------------------------------------ engine programs, whole ---
def _engine_cfg(kind):
    """Small widths and few layers; the pool rows keep the cells' lanes
    (flad-adllm's 8 KV heads of 64, Moonlight's 576-value latent in 640)."""
    from repro.config import ModelConfig
    from repro.configs.moonlight_16b_a3b import CONFIG
    if kind == "kv":
        return ModelConfig(name="kv", family="dense", num_layers=2,
                           d_model=256, num_heads=HQ, num_kv_heads=HKV,
                           head_dim=D, d_ff=512, vocab_size=512,
                           param_dtype="bfloat16")
    return dataclasses.replace(
        CONFIG, num_layers=3, d_model=256, num_heads=4, d_ff=512,
        vocab_size=512, param_dtype="bfloat16",
        moe=dataclasses.replace(CONFIG.moe, num_experts=8, d_expert=128,
                                experts_held=4))


_DTYPE_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "s8": 1, "u32": 4, "pred": 1,
                "u8": 1, "f16": 2}
_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "int8": "s8"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][\w-]*)\(")
_ARRAY = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
# ops that pass a buffer on without moving its bytes
_FORWARDING = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def _array_bytes(result_type):
    for dt, dims in _ARRAY.findall(result_type):
        n = _DTYPE_BYTES[dt]
        for x in filter(None, dims.split(",")):
            n *= int(x)
        yield n


# 4,096 blocks: one layer of a pool is 64 MiB (K/V) or 80 MiB (latent),
# which the compiler cannot stage in VMEM, so a pool-sized result is a
# copy in HBM or the in-place write
@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
@pytest.mark.parametrize("kind", ["kv", "latent"])
def test_engine_writes_donated_pools_in_place(sds, monkeypatch, kind,
                                              program):
    """Every pool is in the compiled program's input-output alias table,
    and no operation but the in-place scatter of the new rows
    (``attention/kv_write``) yields a result as large as one layer of a
    pool: nothing slices, relayouts, copies or writes back a pool."""
    from repro.models import lm
    from repro.serve import PagedCacheSpec, PagedEngine

    # the engine's kernels pick interpret mode from the default backend
    # (the CPU here); compile them with Mosaic for the described chip
    monkeypatch.setattr(ops, "_auto_interpret", bool)
    cfg = _engine_cfg(kind)
    spec = PagedCacheSpec(num_blocks=4096, block_size=BS,
                          max_blocks_per_req=T)
    eng = PagedEngine(cfg, spec, max_context=T * BS, slots=SLOTS)
    as_sds = functools.partial(jax.tree.map, lambda x: sds(x.shape, x.dtype))
    params = as_sds(jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0),
                                                   cfg)))
    pools = as_sds(jax.eval_shape(eng.init_pools))
    def i32(*shape):
        return sds(shape, jnp.int32)

    if program == "decode":
        fn, args = eng._decode, (i32(SLOTS), i32(SLOTS, T), i32(SLOTS))
    else:
        fn, args = eng._prefill_chunk, (i32(32), i32(T), i32(), i32())
    hlo = fn.lower(params, pools, *args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 1

    # the entry parameters of a pool's type (no weight has one), each
    # aliased to an output: ``{out}: (param, {}, may-alias)``
    leaves = jax.tree.leaves(pools)
    types = {_HLO_DTYPE[x.dtype.name] + str(list(x.shape)).replace(" ", "")
             for x in leaves}
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    pool_params = [int(n) for ty, n in re.findall(
        r"= (\w+\[[\d,]+\])\{[^}]*\} parameter\((\d+)\)", entry)
        if ty in types]
    assert len(pool_params) == len(leaves)
    header = hlo[:hlo.index("\n")]
    aliased = {int(n) for n in re.findall(
        r"\(\s*(\d+),\s*\{\}", header[header.index("input_output_alias"):])}
    assert set(pool_params) <= aliased

    layer_bytes = min(x.size // x.shape[0] * x.dtype.itemsize
                      for x in leaves)
    movers, writes = [], 0
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or max(_array_bytes(m.group(2)), default=0) < layer_bytes:
            continue
        name, op = m.group(1), m.group(3)
        if op == "scatter" or (op == "fusion"
                               and 'kv_write/scatter"' in line
                               and "aliasing_operands" in line):
            writes += 1
        elif op not in _FORWARDING:
            movers.append(f"{op} {name}")
    assert writes >= 1
    assert not movers, movers
