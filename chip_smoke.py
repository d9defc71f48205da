#!/usr/bin/env python3
"""Smoke run of FLAD's main paths on a TPU, through ``repro.api.Session``.

    python chip_smoke.py             # one chip: train, kernel, serve
    python chip_smoke.py --chips 4   # four chips: FHDP on a (2, 2) mesh

One chip runs, in one process and at the published flad-adllm widths
(16 layers, d_model 1024, 16/8 heads, head_dim 64, d_ff 4096, vocab
32000, bf16; random weights from a seed):

  * train  -- two ``distill_fl`` rounds (LoRA students through the fused
              Pallas matmul, adapter deltas through the int8 codec
              kernels); every round's loss must be finite;
  * kernel -- flash attention forward and backward at S=1024 against the
              float32 reference;
  * serve  -- the continuous-batching scheduler over the paged KV cache
              (chunked prefill, 8 slots, block size 16), then one
              request's prefill and first decode logits against
              ``lm.forward`` in float32, and the count of Mosaic kernels
              in the compiled decode step.

``--chips 4`` runs only FHDP: flad-vision at full width under the
``pipeline`` strategy, 2 vehicles x 2 stages, two steps, against the loss
of the same parameters on one device.

JAX must find a TPU: on any other platform the script exits non-zero
before any phase. Every phase raises on failure. The last line of stdout
is ``{"ok": true, "device": {...}}``, printed only when all passed. The
persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` in this checkout; the ``[compile]`` line
reports the backend compile seconds of this run and the cache hits.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: flash attention vs the float32 reference, bf16 inputs: max |o - ref|
#: (outputs are O(1)), and max |grad - ref| / max(1, max |ref|)
FLASH_OUT_TOL = 2e-2
FLASH_GRAD_TOL = 5e-2
#: bf16 serving logits vs ``lm.forward`` in float32 on the same weights:
#: max |logit - ref| / max(1, max |ref|)
LOGIT_TOL = 5e-2
#: FHDP step-1 loss vs the flat single-device loss (relative)
FHDP_REL_TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def max_abs(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------- phases ----
def train_phase(*, full: bool = True, shape: str = "128x8",
                rounds: int = 2, warmup: int = 2) -> None:
    from repro.api import MeshSpec, Session
    session = Session("flad-adllm", full=full, strategy="distill_fl",
                      mesh=MeshSpec((1,)), shape=shape,
                      topology="2@nano*2", codec="int8",
                      warmup_steps=warmup)
    out = session.run(rounds)
    warm = session.strategy.warmup_history
    print(f"[train] warmup losses {warm}")
    check(len(warm) == warmup and bool(np.isfinite(warm).all()),
          f"finite warmup losses {warm}")
    check(len(out["history"]) == rounds, f"{rounds} logged rounds")
    for h in out["history"]:
        losses = np.asarray(h["per_client/loss"])
        print(f"[train] round {h['round']} per-client loss "
              f"{losses.tolist()}")
        check(bool(np.isfinite(losses).all()),
              f"finite losses in round {h['round']}")


def flash_phase(*, b: int = 1, hq: int = 16, hkv: int = 8, s: int = 1024,
                d: int = 64, seed: int = 0) -> None:
    from repro.kernels import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(k, (b, hq, s, d)).astype(jnp.bfloat16)
            for k in (ks[0], ks[3]))
    k_, v = (jax.random.normal(k, (b, hkv, s, d)).astype(jnp.bfloat16)
             for k in ks[1:3])

    def fwd_bwd(attn):
        def run(q, k, v, g):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o,) + vjp(g)
        return jax.jit(run)

    got = fwd_bwd(ops.flash_attention_ad)(q, k_, v, g)
    f32 = [x.astype(jnp.float32) for x in (q, k_, v, g)]
    with jax.default_matmul_precision("highest"):
        want = fwd_bwd(ref.flash_attention_ref)(*f32)
    err_o = max_abs(got[0], want[0])
    print(f"[kernel] flash fwd S={s} Hq={hq} Hkv={hkv} D={d}: "
          f"max |o - ref| {err_o:.3e} (tol {FLASH_OUT_TOL})")
    check(err_o <= FLASH_OUT_TOL, "flash forward within tolerance")
    for name, a, r in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        err = max_abs(a, r) / max(1.0, float(jnp.max(jnp.abs(r))))
        print(f"[kernel] flash bwd {name}: max |d - ref| / max(1, |ref|) "
              f"{err:.3e} (tol {FLASH_GRAD_TOL})")
        check(err <= FLASH_GRAD_TOL, f"flash {name} within tolerance")


def serve_phase(cfg, *, slots: int = 8, requests: int = 8,
                max_prompt: int = 512, block_size: int = 16,
                chunk: int = 16, seed: int = 0) -> None:
    from repro.api import MeshSpec, Session
    from repro.models import lm
    from repro.serve import (BlockAllocator, PagedCacheSpec, PagedEngine,
                             generate_fleet_requests)

    params = lm.init(jax.random.PRNGKey(seed), cfg)
    session = Session(cfg=cfg, strategy="tensor", mesh=MeshSpec((1,)),
                      seed=seed)
    report = session.serve(scheduler="continuous", params=params,
                           requests=requests, batch=slots,
                           context=max_prompt, max_prompt=max_prompt,
                           block_size=block_size, prefill_chunk=chunk)
    print(f"[serve] {report['requests']} requests, "
          f"{report['total_new_tokens']} tokens; warm "
          f"{report['warm_tokens_per_s']:.1f} tok/s "
          f"(smoke number, not a benchmark)")
    check(report["requests"] == requests and len(report["sequences"])
          == requests, f"all {requests} requests served")

    # the longest prompt of the served trace, through a fresh engine of
    # the same geometry: prefill chunks, then one decode step
    trace = generate_fleet_requests("nano*2,agx*2", num_requests=requests,
                                    max_prompt=max_prompt, seed=seed,
                                    vocab_size=cfg.vocab_size)
    prompt = max(trace, key=lambda r: len(r.prompt)).prompt
    plen = len(prompt)
    spec = PagedCacheSpec.for_requests(slots, plen + 1,
                                       block_size=block_size)
    engine = PagedEngine(cfg, spec, max_context=plen + 1, slots=slots)
    pools = engine.init_pools()
    blocks = BlockAllocator(spec).alloc(spec.blocks_needed(plen + 1))
    tables = np.zeros((slots, spec.max_blocks_per_req), np.int32)
    tables[0, :len(blocks)] = blocks
    for pos in range(0, plen, chunk):
        clen = min(chunk, plen - pos)
        buf = np.zeros(chunk, np.int32)
        buf[:clen] = prompt[pos:pos + clen]
        logits, pools = engine.prefill_chunk(params, pools,
                                             jnp.asarray(buf),
                                             jnp.asarray(tables[0]), pos,
                                             clen)
    first = logits[0]
    tokens = np.zeros(slots, np.int32)
    tokens[0] = int(jnp.argmax(first))
    ctx = np.zeros(slots, np.int32)            # dead lanes: ctx 0, table 0
    ctx[0] = plen
    dec_args = (params, pools, jnp.asarray(tokens), jnp.asarray(tables),
                jnp.asarray(ctx))
    # the decode donates the pools: keep the shapes to lower it again
    dec_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), dec_args)
    decoded, _ = engine.decode(*dec_args)

    cfg32 = cfg.replace(param_dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    seq = jnp.asarray(np.append(prompt, tokens[0])[None], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = jax.jit(lambda p, t: lm.forward(p, cfg32, t))(p32, seq)
    for name, got, r in (("prefill", first, want[0, plen - 1]),
                         ("decode", decoded[0], want[0, plen])):
        err = max_abs(got, r) / max(1.0, float(jnp.max(jnp.abs(r))))
        print(f"[serve] {name} logits, prompt of {plen} tokens: "
              f"max |logit - f32 ref| / max(1, |ref|) {err:.3e} "
              f"(tol {LOGIT_TOL})")
        check(err <= LOGIT_TOL, f"{name} logits within tolerance")

    hlo = engine._decode.lower(*dec_shapes).compile().as_text()
    n = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"[serve] compiled decode step: {n} tpu_custom_call op(s)")
    check(n >= 1, "the decode step runs a Mosaic kernel")


def fhdp_phase(cfg, *, seq: int = 64, batch: int = 8, mesh=(2, 2),
               seed: int = 0) -> None:
    from repro.api import MeshSpec, Session
    from repro.config import ShapeConfig
    from repro.configs.common import concrete_batch
    from repro.models import build_model

    shape = ShapeConfig("smoke", seq, batch, "train")
    session = Session(cfg=cfg, strategy="pipeline", shape=shape,
                      mesh=MeshSpec(mesh), learning_rate=1e-3)
    key = jax.random.PRNGKey(seed)
    data = concrete_batch(cfg, shape, key)
    # the pipeline init and build_model share the key: identical params
    model = build_model(cfg)
    flat = float(model.loss(model.init(key), data, remat=False)[0])
    step, (pp, opt) = session.build(key)
    pp, opt, m1 = step(pp, opt, data)
    _, _, m2 = step(pp, opt, data)
    loss1, loss2 = float(m1["loss"]), float(m2["loss"])
    rel = abs(loss1 - flat) / max(abs(flat), 1e-6)
    print(f"[fhdp] {cfg.name} mesh {mesh} (data, model): step-1 loss "
          f"{loss1:.6f}, flat single-device {flat:.6f}, rel {rel:.3e} "
          f"(tol {FHDP_REL_TOL}); step-2 loss {loss2:.6f}")
    check(rel <= FHDP_REL_TOL, "FHDP step 1 matches the flat loss")
    check(bool(np.isfinite(loss2)), "FHDP step 2 loss is finite")


# --------------------------------------------------------------- main ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, kernel and serve phases on one chip; "
                         "4: only the FHDP phase on a (2, 2) mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[device] {device}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, found {device['count']}", file=sys.stderr)
        return 1

    from repro.api import load_config
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    compiles = CompileLog()

    if args.chips == 4:
        phases = [("fhdp", lambda: fhdp_phase(
            load_config("flad-vision", full=True)))]
    else:
        phases = [("train", train_phase), ("kernel", flash_phase),
                  ("serve", lambda: serve_phase(
                      load_config("flad-adllm", full=True)))]
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        gc.collect()                  # free the phase's device buffers
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s")
    print(f"[compile] {compiles.compiles} backend compiles, "
          f"{compiles.seconds:.1f}s; {compiles.cache_hits} persistent-cache "
          f"hits ({cache_dir})")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
