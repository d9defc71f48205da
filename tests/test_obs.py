"""Observability layer (repro.obs): the zero-cost contract (tracing
on/off is bitwise invisible to params, event logs, and greedy streams),
byte-deterministic trace JSON, structural validity per
scripts/validate_trace.py, track placement against the event log, the
metrics registry semantics, and the satellite surfaces (history
wall/sim clocks, ``trace_id`` echo, pool-occupancy report stats,
``benchmarks/run.py --list``), and the scheduler's step spans on the
profiler's clock, read back from a ``jax.profiler`` capture."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.comm.events import MobilitySpec, simulate_schedule
from repro.comm.topology import parse_topology
from repro.configs import get_config
from repro.configs.common import reduced
from repro.obs import (Counter, FL_PID, Gauge, Histogram, MetricsRegistry,
                       ProfileOptions, SERVE_PID, Tracer, kernel_cost_args,
                       profiled, resolve_tracer)
from repro.obs.trace import (CLOUD_TID, QUEUE_TID, SPEC_TID, edge_tid,
                             lane_tid, vehicle_tid)
from repro.serve import (PrefillCostModel, ServeRequest,
                         SpecDecodeCostModel, generate_pod_requests,
                         serve_continuous)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPO = parse_topology("2@nano*2,agx*2")
QUIET = dict(log_every=1, log_fn=lambda *a, **k: None)

#: the busiest timing-only schedule: clocked merges, stragglers, DTMC
#: migrations — every span/flow/counter emission path fires
SCHED = dict(clock=0.05, compute_flops=5e9, jitter=0.3,
             migrate_every=0.05, rounds=10, seed=0,
             mobility=MobilitySpec(size=5, radius=1, seed=1))


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_trace", os.path.join(REPO, "scripts", "validate_trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


VT = _load_validator()


def _spans(tracer, name=None):
    return [e for e in tracer.events
            if e["ph"] == "X" and (name is None or e["name"] == name)]


# ---- tracer primitives ----------------------------------------------------

def test_tracer_metadata_dedupes_and_flow_ids_increment():
    tr = Tracer()
    tr.process(FL_PID, "fl", sort_index=1)
    tr.process(FL_PID, "fl", sort_index=1)          # second call: no-op
    tr.track(FL_PID, CLOUD_TID, "cloud")
    tr.track(FL_PID, CLOUD_TID, "cloud")
    assert [e["ph"] for e in tr.events] == ["M", "M", "M"]
    assert tr.flow("a", 0.0, FL_PID, 1, 1.0, FL_PID, 2) == 0
    assert tr.flow("b", 1.0, FL_PID, 2, 2.0, FL_PID, 1) == 1
    f = [e for e in tr.events if e["ph"] == "f"]
    assert all(e["bp"] == "e" for e in f)


def test_tracer_span_units_and_clamping():
    tr = Tracer()
    tr.complete("work", 1.5, 2.0, pid=FL_PID, tid=3)
    tr.complete("tick", 2.0, 2.0, pid=FL_PID, tid=3)   # zero-width ok
    a, b = _spans(tr)
    assert a["ts"] == 1.5e6 and a["dur"] == 0.5e6
    assert b["dur"] == 0.0
    assert VT.validate(tr.events) == []


def test_tracer_serializes_numpy_args_deterministically():
    def build():
        tr = Tracer()
        tr.complete("s", 0.0, np.float64(1.0), pid=1, tid=1,
                    args={"n": np.int64(3), "v": np.float32(0.5),
                          "xs": np.arange(2)})
        return tr
    raw = build().to_bytes()
    assert raw == build().to_bytes()
    ev = json.loads(raw)["traceEvents"][0]
    assert ev["args"] == {"n": 3, "v": 0.5, "xs": [0, 1]}


def test_resolve_tracer_forms():
    assert resolve_tracer(None) == (None, None)
    tr = Tracer()
    assert resolve_tracer(tr) == (tr, None)
    got, path = resolve_tracer("/tmp/t.json")
    assert isinstance(got, Tracer) and path == "/tmp/t.json"


# ---- validator negative cases ---------------------------------------------

@pytest.mark.parametrize("events,needle", [
    ([{"ph": "Z", "name": "x", "pid": 1, "tid": 1, "ts": 0}], "unknown ph"),
    ([{"ph": "X", "name": "", "pid": 1, "tid": 1, "ts": 0, "dur": 1}],
     "missing/empty name"),
    ([{"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0, "dur": -1}],
     "bad dur"),
    ([{"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": -2, "dur": 1}],
     "bad ts"),
    ([{"ph": "X", "name": "x", "pid": "p", "tid": 1, "ts": 0, "dur": 1}],
     "non-integer pid"),
    ([{"ph": "C", "name": "c", "pid": 1, "tid": 0, "ts": 0,
       "args": {"v": "hi"}}], "non-numeric series"),
    ([{"ph": "C", "name": "c", "pid": 1, "tid": 0, "ts": 0, "args": {}}],
     "missing args"),
    ([{"ph": "f", "name": "w", "pid": 1, "tid": 1, "ts": 1, "id": 9,
       "bp": "e"}], "no prior s"),
    ([{"ph": "s", "name": "w", "pid": 1, "tid": 1, "ts": 0, "id": 9},
      {"ph": "s", "name": "w", "pid": 1, "tid": 1, "ts": 1, "id": 9}],
     "reused"),
    ([{"ph": "s", "name": "w", "pid": 1, "tid": 1, "ts": 0, "id": 9}],
     "never finished"),
    ([{"ph": "s", "name": "w", "pid": 1, "tid": 1, "ts": 5, "id": 9},
      {"ph": "f", "name": "w", "pid": 1, "tid": 2, "ts": 1, "id": 9,
       "bp": "e"}], "ends before"),
    ([{"ph": "s", "name": "w", "pid": 1, "tid": 1, "ts": 0, "id": 9},
      {"ph": "f", "name": "w", "pid": 1, "tid": 2, "ts": 1, "id": 9}],
     "bp='e'"),
    ([{"ph": "M", "name": "weird_meta", "pid": 1, "tid": 0, "args": {}}],
     "unknown metadata"),
    ([{"ph": "M", "name": "thread_name", "pid": 1, "tid": 0, "args": {}}],
     "args missing"),
])
def test_validator_rejects(events, needle):
    errors = VT.validate(events)
    assert any(needle in e for e in errors), errors


def test_validator_accepts_empty_and_rejects_bad_top_level(tmp_path):
    assert VT.validate([]) == []
    p = tmp_path / "bad.json"
    p.write_text("[1, 2]")
    assert VT.validate_file(str(p)) == [
        "top level must be an object with 'traceEvents'"]
    assert VT.main([str(p)]) == 1


# ---- event-engine tracing (timing-only schedule) --------------------------

def test_schedule_trace_is_byte_deterministic_and_unobtrusive():
    plain = simulate_schedule(TOPO, **SCHED)
    raws = []
    for _ in range(2):
        tr, reg = Tracer(), MetricsRegistry()
        stats = simulate_schedule(TOPO, tracer=tr, metrics=reg, **SCHED)
        # zero-cost contract: tracing never perturbs the schedule
        assert stats["event_log"] == plain["event_log"]
        assert stats["sim_time_s"] == plain["sim_time_s"]
        raws.append(tr.to_bytes())
    assert raws[0] == raws[1]
    assert VT.validate(json.loads(raws[0])["traceEvents"]) == []
    # fabric metrics rode along
    assert reg.counter("fl_merges").value() == SCHED["rounds"]
    assert reg.histogram("fl_observed_staleness_s").stats()["count"] > 0
    assert reg.counter("fl_uplink_bytes").value(edge="0") > 0


def test_schedule_trace_tracks_match_event_log():
    tr = Tracer()
    stats = simulate_schedule(TOPO, tracer=tr, **SCHED)
    log = stats["event_log"]
    times = {round(t * 1e6, 3) for _, t, *rest in log}

    compute = _spans(tr, "compute")
    assert compute and all(
        e["pid"] == FL_PID and e["tid"] >= vehicle_tid(0) for e in compute)
    # every compute span ends at its LocalStepDone event
    done = {round(t * 1e6, 3) for k, t, *r in log if k == "local_step_done"}
    assert all(round(e["ts"] + e["dur"], 3) in done for e in compute)

    uplink = _spans(tr, "uplink")
    assert uplink and all(e["tid"] >= vehicle_tid(0) for e in uplink)

    backhaul = _spans(tr, "backhaul")
    assert backhaul and all(
        edge_tid(0) <= e["tid"] < vehicle_tid(0) for e in backhaul)

    merges = _spans(tr, "merge")
    assert len(merges) == SCHED["rounds"]
    assert all(e["tid"] == CLOUD_TID and e["dur"] == 0.0 for e in merges)
    assert all(round(e["ts"], 3) in times for e in merges)

    kinds = {e[0] for e in log}
    assert "pod_migration" in kinds
    inst = [e for e in tr.events if e["ph"] == "i"]
    assert {e["name"] for e in inst} >= {"cloud_deadline", "pod_migration"}
    # every emitted flow pairs up and lands on the FL process
    flows = [e for e in tr.events if e["ph"] in ("s", "f")]
    assert flows and all(e["pid"] == FL_PID for e in flows)


# ---- traced model run (async Session) -------------------------------------

def _session(strategy, **kw):
    from repro.api import Session
    return Session("flad-vision", strategy=strategy, mesh=(1,),
                   shape="8x4", topology=TOPO, codec="int8",
                   local_steps=2, seed=3, **kw)


def test_async_run_tracing_is_bitwise_zero_cost():
    """Acceptance: same seed with tracing on/off => identical params and
    event log; same seed traced twice => byte-identical trace JSON; the
    history rides both clocks; the metrics snapshot holds the fabric
    counters next to the loop scalars."""
    from repro.api import LoopHooks
    quiet = LoopHooks(**QUIET)
    opts = dict(clock=0.05, compute_flops=5e9, compute_jitter=0.3,
                migrate_every=0.05,
                mobility=MobilitySpec(size=5, radius=1, seed=1))

    base = _session("async_hier_fl", **opts)
    ref = base.run(8, hooks=quiet)

    runs = []
    for _ in range(2):
        tr, reg = Tracer(), MetricsRegistry()
        ses = _session("async_hier_fl", **opts)
        out = ses.run(8, hooks=quiet, trace=tr, metrics=reg)
        runs.append((ses, out, tr, reg))

    (s1, o1, t1, r1), (_, o2, t2, _) = runs
    assert o1["event_log"] == ref["event_log"] == o2["event_log"]
    for x, y in zip(jax.tree.leaves(base.state[0]),
                    jax.tree.leaves(s1.state[0])):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert t1.to_bytes() == t2.to_bytes()
    assert VT.validate(t1.events) == []
    assert len(_spans(t1, "merge")) == o1["merges"]

    # satellite: history carries wall and simulated clocks
    for h in o1["history"]:
        assert h["t_wall_s"] >= 0.0
        assert h["t_sim_s"] > 0.0
    assert o1["history"][-1]["t_sim_s"] == o1["sim_time_s"]

    snap = r1.snapshot()
    assert snap["schema"] == "repro.obs.metrics/1"
    names = set(snap["metrics"])
    assert {"fl_merges", "fl_uplink_bytes", "fl_backhaul_bytes",
            "fl_observed_staleness_s"} <= names
    assert any(n.startswith("comm_bytes") for n in names)

    # untraced ref run must not have grown a trace/metrics path
    assert "trace_path" not in ref and "trace_path" not in o1


def test_run_trace_rejects_wall_clock_strategies():
    ses = _session("hier_fl")
    with pytest.raises(ValueError, match="async"):
        ses.run(1, trace=Tracer())


# ---- continuous-scheduler tracing -----------------------------------------

@pytest.fixture(scope="module")
def lm_setup():
    from repro.models import lm
    cfg = reduced(get_config("flad_adllm")).replace(param_dtype="float32")
    return cfg, lm.init(jax.random.PRNGKey(0), cfg)


def _serve_opts(cfg):
    """Pod-templated trace (shared prefix, unique suffixes) through the
    chunked + prefix-cache scheduler, with the MAC cost model on the sim
    clock so spans carry ``est_cost_s``."""
    reqs = generate_pod_requests("nano*1,agx*1", num_requests=4, pods=1,
                                 template_len=8, max_suffix=4, seed=0,
                                 short_new=(3, 4), long_new=(5, 6),
                                 long_frac=0.5, vocab_size=cfg.vocab_size)
    return dict(requests=reqs, slots=2, block_size=4, max_context=16,
                prefill="chunked", prefill_chunk=4, prefix_cache=True,
                prefill_cost=PrefillCostModel(), log_fn=None)


def test_serve_tracing_is_bitwise_zero_cost(lm_setup):
    cfg, params = lm_setup
    opts = _serve_opts(cfg)
    plain = serve_continuous(cfg, params=params, **opts)
    raws, reports = [], []
    for _ in range(2):
        tr = Tracer()
        rep = serve_continuous(cfg, params=params, trace=tr, **opts)
        raws.append(tr.to_bytes())
        reports.append(rep)
    rep = reports[0]
    assert rep["sequences"] == plain["sequences"]       # greedy streams
    assert raws[0] == raws[1]

    events = json.loads(raws[0])["traceEvents"]
    assert VT.validate(events) == []
    spans = [e for e in events if e["ph"] == "X"]
    assert spans and all(e["pid"] == SERVE_PID for e in spans)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert set(by_name) >= {"queued", "prefill_chunk", "decode"}
    assert all(e["tid"] == QUEUE_TID for e in by_name["queued"])
    assert all(e["tid"] >= lane_tid(0) for e in by_name["prefill_chunk"])
    assert len(by_name["queued"]) == len(by_name["decode"]) == 4
    assert [e for e in events if e["ph"] == "i" and e["name"] ==
            "first_token"]

    # trace_id echoes through every request-scoped span, and the chunk
    # spans carry the MAC cost model's annotations
    ids = {e["args"]["trace_id"] for e in by_name["queued"]}
    assert ids == {0, 1, 2, 3}
    for e in by_name["prefill_chunk"]:
        assert e["args"]["trace_id"] in ids
        assert e["args"]["padded_tokens"] > 0
        assert e["args"]["est_cost_s"] > 0.0
    # prefix sharing is annotated where it happened
    assert any(e["args"].get("shared_blocks", 0) > 0
               for e in by_name["queued"])

    # satellite: pool-occupancy stats in the loadgen report
    assert rep["pool_blocks_peak"] >= rep["pool_blocks_mean"] > 0.0
    assert rep["pool_blocks_peak"] == plain["pool_blocks_peak"]
    # and a kv-block counter track sampled alongside
    assert any(e["ph"] == "C" and e["name"] == "kv blocks" for e in events)


def test_speculative_serve_tracing_and_metrics(lm_setup):
    """Speculative mode keeps the zero-cost contract: draft/verify spans
    land on the SPEC_TID track, the trace is byte-deterministic, and the
    traced streams stay bitwise equal to an untraced run. The
    accepted-draft-length histogram and preemption counter are in the
    scheduler's always-on registry snapshot from construction."""
    from repro.serve import ContinuousScheduler, PagedCacheSpec, PagedEngine
    cfg, params = lm_setup
    opts = _serve_opts(cfg)
    opts["prefill_cost"] = SpecDecodeCostModel()
    plain = serve_continuous(cfg, params=params, speculative=True,
                             draft_k=3, **opts)
    raws, rep = [], None
    for _ in range(2):
        tr = Tracer()
        rep = serve_continuous(cfg, params=params, speculative=True,
                               draft_k=3, trace=tr, **opts)
        raws.append(tr.to_bytes())
    assert rep["sequences"] == plain["sequences"]
    assert raws[0] == raws[1]
    events = json.loads(raws[0])["traceEvents"]
    assert VT.validate(events) == []
    spec_spans = [e for e in events
                  if e["ph"] == "X" and e["tid"] == SPEC_TID]
    assert {e["name"] for e in spec_spans} == {"draft", "verify"}
    assert all(e["pid"] == SERVE_PID for e in spec_spans)
    assert sum(e["name"] == "verify" for e in spec_spans) \
        == rep["spec_steps"]
    assert all(e["args"]["forwards"] == 4 for e in spec_spans
               if e["name"] == "draft")
    acc = sum(e["args"]["accepted_drafts"] for e in spec_spans
              if e["name"] == "verify")
    assert acc == rep["accepted_drafts"]
    # the specdec track is named
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               and e["tid"] == SPEC_TID
               and e["args"]["name"] == "specdec" for e in events)

    # satellite: always-on registry carries the speculative instruments
    # the moment the scheduler is built — no samples needed
    reg = MetricsRegistry()
    pspec = PagedCacheSpec.for_requests(1, 16, block_size=4)
    eng = PagedEngine(cfg, pspec, max_context=8, slots=1)
    sched = ContinuousScheduler(eng, params, speculative=True, draft_k=3,
                                prefix_cache=True, metrics=reg)
    snap = reg.snapshot()["metrics"]
    assert snap["serve_spec_accepted_len"]["type"] == "histogram"
    assert snap["serve_preemptions"]["type"] == "counter"
    # and a drained run populates the histogram
    rng = np.random.default_rng(0)
    sched.run_to_completion(
        [ServeRequest(rid=0,
                      prompt=rng.integers(1, cfg.vocab_size,
                                          (4,)).astype(np.int32),
                      max_new_tokens=6)])
    series = reg.snapshot()["metrics"]["serve_spec_accepted_len"]["series"]
    assert series and series[0]["count"] > 0


# ---- scheduler spans on the profiler's clock ------------------------------

STEP_SPANS = ("scheduler.admit", "scheduler.prefill", "scheduler.decode",
              "scheduler.tokens", "scheduler.commit")


def _span_scheduler(cfg, params):
    """Chunked prefill over 4-token chunks; request 0 fills one chunk and
    request 1 two, so steps read 2, 1 and 2 tokens (a prefill's first
    token beside the decode's). The sampler counts its calls."""
    from repro.serve import ContinuousScheduler, PagedCacheSpec, PagedEngine
    spec = PagedCacheSpec.for_requests(2, 16, block_size=4)
    eng = PagedEngine(cfg, spec, max_context=16, slots=2)
    sched = ContinuousScheduler(eng, params, prefill="chunked",
                                prefill_chunk=4)
    sample, calls = sched.sampler, []

    def counted(logits, key):
        calls.append(len(calls))
        return sample(logits, key)
    sched.sampler = counted
    rng = np.random.default_rng(3)
    for rid, (plen, new) in enumerate([(4, 5), (6, 3)]):
        sched.submit(ServeRequest(
            rid=rid, max_new_tokens=new,
            prompt=rng.integers(1, cfg.vocab_size, (plen,)).astype(np.int32)))
    return sched, calls


@pytest.fixture(scope="module")
def step_capture(lm_setup, tmp_path_factory):
    """Four scheduler steps inside a ``jax.profiler`` capture, each call
    wrapped in a ``test.step`` span, and the same four steps of a twin
    scheduler without one. Returns the host spans read back from the
    ``.xplane.pb`` (name, start_ns, end_ns; sorted), the token reads per
    step, and both schedulers' streams."""
    import glob
    from jax.profiler import ProfileData, TraceAnnotation
    cfg, params = lm_setup
    steps = 4
    plain, _ = _span_scheduler(cfg, params)
    for i in range(steps):
        plain.step(float(i))
    sched, calls = _span_scheduler(cfg, params)
    out = str(tmp_path_factory.mktemp("xplane"))
    reads = []
    with jax.profiler.trace(out):
        for i in range(steps):
            n = len(calls)
            with TraceAnnotation("test.step"):
                sched.step(float(i))
            reads.append(len(calls) - n)
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    spans = sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns)
         for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name in STEP_SPANS + ("test.step",)),
        key=lambda s: s[1])
    streams = lambda s: {r.rid: list(r.tokens) for r in
                         list(s.finished) + [r for r in s.active if r]}
    return dict(spans=spans, reads=reads, steps=steps,
                traced=streams(sched), plain=streams(plain))


def _per_step(cap):
    """The program's spans grouped by the ``test.step`` call they fall in."""
    calls = [s for s in cap["spans"] if s[0] == "test.step"]
    return [[s for s in cap["spans"] if s[0] != "test.step"
             and t0 <= s[1] and s[2] <= t1] for _, t0, t1 in calls]


def test_span_is_the_profilers_trace_annotation():
    from repro.obs import span
    with span("scheduler.admit") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_scheduler_opens_every_step_with_one_admit_span(step_capture):
    per_step = _per_step(step_capture)
    assert len(per_step) == step_capture["steps"]
    assert [sum(n == "scheduler.admit" for n, _, _ in st)
            for st in per_step] == [1] * step_capture["steps"]
    assert all(st[0][0] == "scheduler.admit" for st in per_step)
    # every program span lies inside a step call
    assert sum(map(len, per_step)) == sum(
        s[0] != "test.step" for s in step_capture["spans"])


def test_scheduler_step_spans_are_disjoint_and_cover_the_step(step_capture):
    calls = [s for s in step_capture["spans"] if s[0] == "test.step"]
    for (_, t0, t1), st in zip(calls, _per_step(step_capture)):
        for a, b in zip(st, st[1:]):
            assert a[2] <= b[1], (a, b)
        covered = sum(e - s for _, s, e in st)
        assert covered >= 0.9 * (t1 - t0)
        assert {n for n, _, _ in st} <= set(STEP_SPANS)


def test_scheduler_tokens_span_per_token_read(step_capture):
    per_step = _per_step(step_capture)
    tokens = [sum(n == "scheduler.tokens" for n, _, _ in st)
              for st in per_step]
    assert tokens == step_capture["reads"]
    assert tokens[:3] == [2, 1, 2]     # a prefill's first token + decode
    commits = [sum(n == "scheduler.commit" for n, _, _ in st)
               for st in per_step]
    assert commits == tokens
    # each token read is followed by its commit
    for st in per_step:
        names = [n for n, _, _ in st]
        for i, n in enumerate(names):
            if n == "scheduler.tokens":
                assert names[i + 1] == "scheduler.commit"


def test_scheduler_spans_leave_greedy_streams_unchanged(step_capture):
    assert step_capture["traced"] == step_capture["plain"]
    assert {rid: len(t) for rid, t in step_capture["traced"].items()} \
        == {0: 5, 1: 3}


def test_serve_request_trace_id_defaults_to_rid():
    prompt = np.zeros(3, np.int32)
    assert ServeRequest(7, prompt, 2).trace_id == 7
    assert ServeRequest(7, prompt, 2, trace_id=41).trace_id == 41


def test_session_serve_trace_needs_continuous_scheduler():
    from repro.api import Session
    ses = Session("flad-adllm", mesh=(1,), shape="8x4")
    with pytest.raises(ValueError, match="continuous"):
        ses.serve(trace=Tracer())


# ---- metrics registry -----------------------------------------------------

def test_counter_is_monotone_and_labeled():
    c = Counter("bytes")
    c.inc(3, edge="0")
    c.inc(4, edge="0")
    c.inc(1, edge="1")
    assert c.value(edge="0") == 7.0 and c.value(edge="1") == 1.0
    assert c.value(edge="9") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1, edge="0")


def test_gauge_tracks_high_watermark():
    g = Gauge("pool")
    for v in (3, 9, 5):
        g.set(v)
    assert g.stats() == {"last": 5.0, "mean": 17.0 / 3, "count": 3,
                         "peak": 9.0, "min": 3.0}
    assert g.stats(other="label") is None


def test_histogram_buckets_and_sum():
    h = Histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0, 3.0):
        h.observe(v)
    s = h.stats()
    assert s["count"] == 4 and s["sum"] == pytest.approx(5.55)
    assert [b["count"] for b in s["buckets"]] == [1, 1, 2]
    assert s["buckets"][-1]["le"] == "inf"


def test_registry_publish_scalars_and_type_conflicts():
    reg = MetricsRegistry()
    reg.publish_scalars({"loss": 0.5, "comm_bytes_uplink": 100,
                         "per_client/loss": np.zeros(4)})
    reg.publish_scalars({"loss": 0.25, "comm_bytes_uplink": 50})
    assert reg.counter("comm_bytes_uplink").value() == 150.0
    assert reg.gauge("loss").stats()["last"] == 0.25
    assert reg.get("per_client/loss") is None          # arrays skipped
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("loss")
    assert len(reg) == 2


def test_registry_snapshot_roundtrips_to_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc(2, pod="a")
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(0.2)
    path = str(tmp_path / "metrics.json")
    reg.save(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == "repro.obs.metrics/1"
    assert doc["metrics"]["c"]["series"] == [
        {"labels": {"pod": "a"}, "value": 2.0}]
    assert doc["metrics"]["g"]["type"] == "gauge"


# ---- profiling hooks ------------------------------------------------------

def test_profiled_disabled_is_a_noop():
    with profiled(None):
        pass
    with profiled(ProfileOptions()):        # jax_trace_dir=None
        pass


def test_profiled_capture_without_profiler_raises(monkeypatch, tmp_path):
    """A requested capture is never silently dropped."""
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    with pytest.raises(ImportError):
        with profiled(ProfileOptions(jax_trace_dir=str(tmp_path))):
            pass


def test_kernel_cost_args_prices_through_the_cost_model():
    cm = PrefillCostModel(s_per_token=1e-3, s_per_mac=1e-6)
    args = kernel_cost_args(padded_tokens=10, attn_mac=100, cost_model=cm)
    assert args["padded_tokens"] == 10 and args["attn_mac"] == 100
    assert args["est_cost_s"] == pytest.approx(10 * 1e-3 + 100 * 1e-6)
    assert kernel_cost_args() == {}
    assert kernel_cost_args(flops=5e9) == {"flops": 5e9}
    assert "est_cost_s" not in kernel_cost_args(flops=1.0, cost_model=cm)


# ---- benchmark registry listing -------------------------------------------

def test_benchmarks_list_prints_registry():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--list"], capture_output=True, text=True, timeout=60, cwd=REPO)
    assert out.returncode == 0, out.stderr
    names = out.stdout.split()
    assert len(names) == 15 and len(set(names)) == 15
    assert {"serving", "prefill", "async", "comm", "specdec"} <= set(names)
