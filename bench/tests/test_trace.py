import json
import types
from pathlib import Path

import pytest

from bench.harness import trace as T
from bench.harness.loader import metric_reader

DATA = Path(__file__).with_name("data")


@pytest.fixture
def tr():
    return T.Trace.from_json(json.loads((DATA / "trace_small.json")
                                        .read_text()))


def test_busy_is_the_union_inside_the_window(tr):
    # [1, 5] + [6, 7] + [9.5, 10]: nesting counted once, the op before the
    # window left out, the one crossing its end clipped
    assert T.busy_seconds(tr, "/device:TPU:0") == pytest.approx(5.5)
    assert T.busy_seconds(tr, "/device:TPU:1") == pytest.approx(10.0)
    assert T.mean_busy_seconds(tr) == pytest.approx(7.75)


def test_idle_gaps_and_what_the_host_did(tr):
    gaps = T.idle_gaps(tr, "/device:TPU:0")
    assert gaps == [(0.0, 1.0), (5.0, 6.0), (7.0, 9.5)]
    by = T.attribute(gaps, tr.host)
    assert by == pytest.approx({"driver.wait_arrival": 1.0,
                                "engine.sample": 1.0, "(no span)": 2.5})


def test_kernel_time_by_name(tr):
    ev = T.matching(tr.ops["/device:TPU:0"], "paged_decode", tr.window)
    assert sum(e.dur for e in ev) == pytest.approx(1.0)


def test_self_times_and_top_ops(tr):
    top = dict(T.top_ops(tr, "/device:TPU:0"))
    assert top == pytest.approx({"while.1": 1.0, "fusion.2": 1.0,
                                 "_paged_decode_kernel": 1.0,
                                 "fusion.3": 1.0, "copy.4": 1.0,
                                 "fusion.5": 0.5})
    b = T.breakdown(tr)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0] == ["(no span)", pytest.approx(2.5)]


def test_short_names_of_hlo_ops():
    long = ("%copy.67 = bf16[8,12288,16,64]{3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[8,12288,16,64]{3,0,2,1:T(8,128)(2,1)} %fusion.123)")
    assert T.short_name(long) == "copy.67 bf16[8,12288,16,64] copy"
    k = ("%paged_decode_attention.5 = bf16[32,8,2,64]{3,2,1,0:T(2,128)"
         "(2,1)S(1)} custom-call(s32[32,144]{1,0:T(8,128)S(1)} %copy-done.6)")
    assert T.short_name(k) == ("paged_decode_attention.5 bf16[32,8,2,64] "
                               "custom-call")
    assert T.short_name("fusion.2") == "fusion.2"


def test_union_of_unsorted_overlaps():
    assert T.union([(3, 4), (0, 2), (1, 3), (5, 5)]) == [(0, 4)]


def test_module_and_idle_readers(tr):
    data = types.SimpleNamespace(trace=tr)
    assert metric_reader("decode_step_ms").read(data) == pytest.approx(2000.0)
    assert metric_reader("prefill_chunk_ms").read(data) == pytest.approx(
        1000.0)
    assert metric_reader("idle_share.serve").read(data) == pytest.approx(
        100.0 * (1 - 7.75 / 10.0))


def test_readers_are_silent_without_their_events(tr):
    empty = T.Trace((0.0, 1.0), {}, {}, [])
    data = types.SimpleNamespace(trace=empty)
    for name in ("decode_step_ms", "prefill_chunk_ms", "idle_share.serve"):
        assert metric_reader(name).read(data) is None


def test_round_trip(tr):
    assert T.Trace.from_json(tr.to_json()) == tr
