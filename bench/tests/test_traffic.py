import numpy as np
import pytest

from bench.harness import traffic as T
from bench.harness.loader import Cell

BIG = 2 ** 40 + 12345

#: a pod mix as a later cell would state it: bursts from four pods, each
#: with a 1536-token preamble
POD_MIX = {
    "driver": "serve", "shape_seed": 2511090252, "knee_per_s": 0.5,
    "load": 0.8,
    "arrivals": {"process": "bursts", "burst_min": 4, "burst_max": 12,
                 "burst_span_s": 0.2},
    "pods": {"count": 4, "preamble_tokens": 1536},
    "prompt": {"dist": "uniform", "min": 32, "max": 256},
    "output": [{"share": 0.8, "min": 8, "max": 32},
               {"share": 0.2, "min": 128, "max": 256}],
}


@pytest.fixture(params=["fleet", "pods"])
def mix(request):
    if request.param == "pods":
        return POD_MIX
    return Cell("adllm-serve-fleet").traffic


def test_every_seed_gets_the_same_work(mix):
    a = T.schedule(mix, 20.0, 1, 32000)
    b = T.schedule(mix, 20.0, BIG, 32000)
    assert len(a) == len(b) > 0
    # the same sizes at the same times, in the same order
    assert [(r.due_s, len(r.prompt), r.max_new_tokens, r.pod) for r in a] \
        == [(r.due_s, len(r.prompt), r.max_new_tokens, r.pod) for r in b]
    # but other tokens
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_same_seed_same_schedule(mix):
    a = T.schedule(mix, 10.0, BIG, 32000)
    b = T.schedule(mix, 10.0, BIG, 32000)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_due_times_open_loop_in_window(mix):
    rs = T.schedule(mix, 600.0, 7, 32000)
    due = np.array([r.due_s for r in rs])
    assert (np.diff(due) >= 0).all() and due[0] >= 0 and due[-1] < 600.0
    assert len(rs) == pytest.approx(T.rate(mix) * 600.0, rel=0.25)
    assert [r.rid for r in rs] == list(range(len(rs)))


def test_rate_is_load_times_knee():
    mix = Cell("adllm-serve-fleet").traffic
    assert "rate_per_s" not in mix["arrivals"]
    assert T.rate(mix) == mix["load"] * mix["knee_per_s"]
    rs = T.schedule(mix, 100.0, 1, 32000, rate_per_s=1.0)
    assert len(rs) == 100


def test_lengths_within_the_mix(mix):
    rs = T.schedule(mix, 30.0, 3, 32000)
    pre = mix["pods"]["preamble_tokens"] if mix.get("pods") else 0
    for r in rs:
        s = len(r.prompt) - pre
        assert mix["prompt"]["min"] <= s <= mix["prompt"]["max"]
        assert any(o["min"] <= r.max_new_tokens <= o["max"]
                   for o in mix["output"])
        assert len(r.prompt) + r.max_new_tokens <= T.max_request_tokens(mix)
        assert r.prompt.dtype == np.int32 and r.prompt.min() >= 1


def test_pods_share_their_preamble():
    rs = T.schedule(POD_MIX, 600.0, 5, 32000)
    n = POD_MIX["pods"]["preamble_tokens"]
    by_pod = {}
    for r in rs:
        assert r.shared_tokens == n and r.pod >= 0
        by_pod.setdefault(r.pod, []).append(r.prompt[:n])
    assert len(by_pod) > 1
    for pres in by_pod.values():
        assert all((p == pres[0]).all() for p in pres)
    firsts = [p[0] for p in by_pod.values()]
    assert not all((f == firsts[0]).all() for f in firsts[1:])


def test_bursts_come_from_one_pod_within_their_span():
    rs = T.schedule(POD_MIX, 600.0, 9, 32000)
    span = POD_MIX["arrivals"]["burst_span_s"]
    # requests closer than a burst span to their neighbour mostly share a pod
    close = [(a.pod == b.pod) for a, b in zip(rs, rs[1:])
             if b.due_s - a.due_s < span / 4]
    assert sum(close) >= 0.8 * len(close)


def test_output_mix_shares():
    mix = Cell("adllm-serve-fleet").traffic
    rs = T.schedule(mix, 50.0, 2, 32000)
    # a share's count is rounded down; the first part takes the rest
    assert sum(r.max_new_tokens >= 128 for r in rs) == int(0.2 * len(rs))
