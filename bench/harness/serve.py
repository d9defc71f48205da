"""Open-loop serving driver: ``ContinuousScheduler.step`` over a
``PagedEngine``, on the host's wall clock.

Set-up builds the engine once (block size and prefill chunk are the
program's own defaults), makes the weights on the device from the seed,
and warms every program the window will run: one prefill chunk and one
decode step (both into the reserved null block), the greedy sampler at
both logit shapes, and, where the mix shares prefixes, the block copy.

The window submits each request when it is due (open loop: a slow
server does not slow the schedule), steps the scheduler while there is
work, and sleeps until the next due time when there is none. A token
reaches the host when the step that made it returns. After the window
closes, no more requests are sent and the scheduler drains what was
sent, for at most ``DRAIN_S`` seconds. TTFT runs from the due time to
the first token, the inter-token gap between consecutive tokens of a
request, and throughput counts the tokens that reached the host inside
the window.

The benchmark's spans (``jax.profiler.TraceAnnotation``) wrap its own
calls into each layer: ``scheduler.step``, ``driver.submit``,
``driver.wait_arrival``, ``driver.record``, and, wrapped on the engine
instance, ``engine.prefill_chunk``, ``engine.decode``,
``engine.copy_block`` and ``engine.sample`` (the sampler and the wait
for its tokens).
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import traffic as T

DRAIN_S = 60.0
#: the traced stretch of a ``--trace 1`` window: its start, as a share
#: of the window, and its length in seconds (at most the rest)
TRACE_AT = 0.4
TRACE_SECONDS = 5.0


def program_defaults() -> dict:
    """Block size and prefill chunk as the program sets them by default."""
    from repro.serve import ContinuousScheduler, PagedCacheSpec

    bs = inspect.signature(PagedCacheSpec.for_requests).parameters[
        "block_size"].default
    chunk = inspect.signature(ContinuousScheduler.__init__).parameters[
        "prefill_chunk"].default
    return {"block_size": int(bs), "prefill_chunk": int(chunk)}


def key_from_seed(seed: int):
    """A JAX key from any whole number, also one beyond 32 bits."""
    seed = abs(int(seed))
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@dataclasses.dataclass
class Rec:
    """What the host saw of one request."""
    rid: int
    due: float
    prompt_len: int
    max_new: int
    shared: int
    submit: Optional[float] = None
    admit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    computed: int = 0          # prompt tokens its prefill chunks computed
    prompt: Optional[np.ndarray] = None
    tokens: Optional[List[int]] = None


@dataclasses.dataclass
class Window:
    """Everything a run's metrics are read from."""
    seconds: float
    recs: Dict[int, Rec]
    prefill_calls: List[tuple]     # (t, q_offset, chunk_len, last, traced)
    decode_calls: List[tuple]      # (t, attended positions per live lane,
                                   #  traced)
    step_walls: List[tuple]        # (t_start, seconds) of steps with work
    end: float                     # seconds from window start to drained
    compiles: int                  # backend compiles inside the window


class ServeDriver:
    """The program's engine and scheduler for one configuration file
    ``cfg`` (sizes and ``deployment``) under one mix; ``model_cfg`` is
    the program's own configuration object for ``cfg``."""

    def __init__(self, cfg: dict, traffic: dict, model_cfg):
        from repro.serve import PagedCacheSpec, PagedEngine

        self.cfg = cfg
        self.traffic = traffic
        self.model_cfg = model_cfg
        dep = cfg["deployment"]
        self.defaults = program_defaults()
        bs = self.defaults["block_size"]
        self.max_tokens = T.max_request_tokens(traffic)
        per_req = -(-self.max_tokens // bs)
        hd = cfg["head_dim"] or cfg["d_model"] // cfg["num_heads"]
        block_bytes = (2 * cfg["num_layers"] * cfg["num_kv_heads"] * bs * hd
                       * jnp.dtype(dep["kv_dtype"]).itemsize)
        self.num_blocks = int(cfg["kv_pool_gib"] * 2 ** 30 // block_bytes)
        self.spec = PagedCacheSpec(num_blocks=self.num_blocks,
                                   block_size=bs,
                                   max_blocks_per_req=per_req)
        self.slots = int(dep["slots"])
        self.engine = PagedEngine(self.model_cfg, self.spec,
                                  max_context=per_req * bs,
                                  slots=self.slots)
        self.prefix = bool(dep["prefix_cache"])
        self.sched = None
        self.traced = False
        self._instrument()

    def describe(self) -> str:
        return (f"block_size {self.spec.block_size} prefill_chunk "
                f"{self.defaults['prefill_chunk']} slots {self.slots} "
                f"kv_pool_blocks {self.num_blocks} "
                f"({self.num_blocks * self.spec.block_size} tokens) "
                f"table {self.spec.max_blocks_per_req} blocks "
                f"prefix_cache {self.prefix}")

    # ---- weights and scheduler ---------------------------------------
    def weights(self, ref, seed: int):
        return jax.jit(lambda k: ref.init_params(self.cfg, k))(
            key_from_seed(seed))

    def scheduler(self, params):
        from repro.serve import ContinuousScheduler

        dep = self.cfg["deployment"]
        s = ContinuousScheduler(self.engine, params, prefill="chunked",
                                prefix_cache=self.prefix,
                                sampling=dep["sampling"], seed=0)
        orig = s.sampler

        def sample(logits, key):
            with jax.profiler.TraceAnnotation("engine.sample"):
                out = orig(logits, key)
                out.block_until_ready()
                return out
        s.sampler = sample
        self.sched = s
        return s

    def _instrument(self) -> None:
        e = self.engine
        pf, dec, cp = e.prefill_chunk, e.decode, e.copy_block
        self.prefill_calls: list = []
        self.decode_calls: list = []

        def prefill_chunk(params, pools, tokens, table, q_offset, chunk_len):
            s = self.sched
            rid, last = -1, True          # warm-up: no request
            if s._prefill_queue:
                slot = s._prefill_queue[0]
                rid = s.active[slot].rid
                last = q_offset + chunk_len == len(s._chain[slot])
            self.prefill_calls.append((time.perf_counter(), rid,
                                       int(q_offset), int(chunk_len), last,
                                       self.traced))
            with jax.profiler.TraceAnnotation("engine.prefill_chunk"):
                return pf(params, pools, tokens, table, q_offset, chunk_len)

        def decode(params, pools, tokens, tables, ctx_lens):
            s = self.sched
            ready = np.array([r is not None and bool(d) for r, d in
                              zip(s.active, s.prefill_done)])
            self.decode_calls.append((time.perf_counter(),
                                      s.ctx[ready].astype(np.int64) + 1,
                                      self.traced))
            with jax.profiler.TraceAnnotation("engine.decode"):
                return dec(params, pools, tokens, tables, ctx_lens)

        def copy_block(pools, src, dst):
            with jax.profiler.TraceAnnotation("engine.copy_block"):
                return cp(pools, src, dst)

        e.prefill_chunk, e.decode, e.copy_block = prefill_chunk, decode, \
            copy_block

    def warm(self, sched) -> None:
        """Run every program of the window once, into the null block."""
        e, p, c = self.engine, sched.params, self.defaults["prefill_chunk"]
        table = jnp.zeros(self.spec.max_blocks_per_req, jnp.int32)
        logits, sched.pools = e.prefill_chunk(p, sched.pools,
                                              jnp.zeros(c, jnp.int32),
                                              table, 0, 1)
        int(sched.sampler(logits, sched._next_key())[0])
        z = jnp.zeros(self.slots, jnp.int32)
        logits, sched.pools = e.decode(
            p, sched.pools, z, jnp.zeros((self.slots,
                                          self.spec.max_blocks_per_req),
                                         jnp.int32), z)
        np.asarray(sched.sampler(logits, sched._next_key()), np.int32)
        if self.prefix:
            sched.pools = e.copy_block(sched.pools, 0, 0)
        jax.block_until_ready(sched.pools)
        self.prefill_calls.clear()
        self.decode_calls.clear()

    # ---- the window --------------------------------------------------
    def run_window(self, sched, reqs: List[T.Request], seconds: float,
                   compile_log, capture=None, drain_s: float = DRAIN_S
                   ) -> Window:
        from repro.serve import ServeRequest

        TA = jax.profiler.TraceAnnotation
        recs = {r.rid: Rec(r.rid, r.due_s, len(r.prompt), r.max_new_tokens,
                           r.shared_tokens) for r in reqs}
        live: Dict[int, object] = {}
        steps = []
        n, i, fin_seen = len(reqs), 0, 0
        trace_at = TRACE_AT * seconds
        trace_end = min(trace_at + TRACE_SECONDS, seconds)
        c0 = compile_log.compiles
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if capture is not None:
                if not capture.started and now >= trace_at:
                    capture.start()
                    self.traced = True
                elif self.traced and now >= trace_end:
                    capture.stop()
                    self.traced = False
            while i < n and reqs[i].due_s <= now:
                r = reqs[i]
                with TA("driver.submit"):
                    sr = ServeRequest(rid=r.rid, prompt=r.prompt,
                                      max_new_tokens=r.max_new_tokens,
                                      arrival_s=r.due_s)
                    sched.submit(sr)
                    live[r.rid] = sr
                    recs[r.rid].submit = now
                i += 1
            if sched.idle:
                if i >= n:
                    if now < seconds:         # the window lasts its length
                        time.sleep(seconds - now)
                    break
                wake = reqs[i].due_s
                if capture is not None and not capture.started:
                    wake = min(wake, trace_at)
                elif self.traced:
                    wake = min(wake, trace_end)
                with TA("driver.wait_arrival"):
                    time.sleep(max(0.0, wake - (time.perf_counter() - t0)))
                continue
            if now > seconds + drain_s:
                break
            np_, nd = len(self.prefill_calls), len(self.decode_calls)
            ts = time.perf_counter()
            with TA("scheduler.step"):
                sched.step(now)
            te = time.perf_counter()
            if len(self.prefill_calls) > np_ or len(self.decode_calls) > nd:
                steps.append((ts - t0, te - ts))
            t = te - t0
            with TA("driver.record"):
                touched = [r for r in sched.active if r is not None]
                touched += sched.finished[fin_seen:]
                fin_seen = len(sched.finished)
                for r in touched:
                    rec = recs[r.rid]
                    k = len(r.tokens)
                    rec.times.extend([t] * (k - len(rec.times)))
                    if rec.admit is None and r.t_admit is not None:
                        rec.admit = r.t_admit
                    if r.t_done is not None and not rec.done:
                        rec.done = True
                        rec.prompt = np.asarray(r.prompt)
                        rec.tokens = list(r.tokens)
        end = time.perf_counter() - t0
        if self.traced:
            capture.stop()
            self.traced = False
        for rid, sr in live.items():     # admitted but never finished
            if recs[rid].admit is None and sr.t_admit is not None:
                recs[rid].admit = sr.t_admit
        for call in self.prefill_calls:
            recs[call[1]].computed += call[3]
        pre = [(c[0] - t0, c[2], c[3], c[4], c[5])
               for c in self.prefill_calls]
        dec = [(c[0] - t0, c[1], c[2]) for c in self.decode_calls]
        self.last_window = Window(seconds, recs, pre, dec, steps, end,
                                  compile_log.compiles - c0)
        return self.last_window

    def release(self) -> None:
        """Drop the scheduler and its pools before the reference runs."""
        if self.sched is not None:
            self.sched.pools = None
        self.sched = None


# ---- end-to-end metrics ------------------------------------------------
def end_to_end(w: Window) -> dict:
    from bench.harness.stats import percentile

    ttft, itl = [], []
    tokens_in_window = 0
    for rec in w.recs.values():
        if rec.times:
            ttft.append(rec.times[0] - rec.due)
        else:                         # never started: waited at least this
            ttft.append(w.end - rec.due)
        itl.extend(np.diff(rec.times))
        tokens_in_window += sum(t < w.seconds for t in rec.times)
    return {"ttft_p90_ms": percentile(ttft, 90) * 1e3,
            "itl_p95_ms": percentile(itl, 95) * 1e3,
            "serve_tokens_per_s": tokens_in_window / w.seconds}


def model_flops(cfg: dict, w: Window, traced_only: bool = False) -> dict:
    """Model operations the window's prefill chunks and decode steps
    needed: prompt tokens computed (not served from cache) and decode
    tokens, attention at their context lengths, the output head only for
    tokens whose logits are used."""
    from bench.harness.flops import token_flops

    pre = dec = 0.0
    for t, q0, clen, last, traced in w.prefill_calls:
        if (traced_only and not traced) or t >= w.seconds:
            continue
        att = q0 + np.arange(1, clen + 1)
        head = np.zeros(clen, bool)
        head[-1] = last
        pre += float(token_flops(cfg, att, head).sum())
    for t, att, traced in w.decode_calls:
        if (traced_only and not traced) or t >= w.seconds:
            continue
        dec += float(token_flops(cfg, att, np.ones(len(att), bool)).sum())
    return {"prefill": pre, "decode": dec}


def busy_wall_seconds(w: Window) -> float:
    """Wall seconds of the scheduler steps inside the window that
    launched device work."""
    return sum(d for t, d in w.step_walls if t < w.seconds)
