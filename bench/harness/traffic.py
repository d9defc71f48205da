"""One open-loop request schedule generator for every serving mix.

A mix is a JSON file of parameters (``traffic/<mix>.json``). Everything
that sets how much work a window holds and when it comes (due times,
prompt and output lengths, which pod a burst comes from) is drawn from
the mix's own ``shape_seed``, so every run of a cell serves the same
requests at the same times. The run's ``--seed`` draws only the token
ids (and the pods' preambles), and with them the weights elsewhere.

The offered rate is ``knee_per_s * load`` from the mix (the knee found
by ``bench/sweep.py``); a sweep passes its own rate.

An arrival process is ``arrivals/<process>.py`` beside this file and a
length distribution ``lengths/<dist>.py``, each with a ``draw``
function: a new process or distribution is a new file. Prompts follow
``prompt`` (with ``pods``, a prompt is its pod's preamble followed by a
unique suffix of that length); output lengths are a mixture of length
specs, each with its ``share`` (``dist`` defaults to uniform).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import List, Optional

import numpy as np

from bench.harness.loader import load_module

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float            # open-loop send time, from the window start
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int
    pod: int = -1
    shared_tokens: int = 0  # leading tokens shared with the pod's preamble


def rng(seed: int) -> np.random.Generator:
    """Any whole number (also above 2**32) seeds a generator."""
    return np.random.default_rng(abs(int(seed)))


def rate(traffic: dict) -> float:
    """Offered requests per second: the mix's load times its knee."""
    return float(traffic["knee_per_s"]) * float(traffic["load"])


def _lengths(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    mod = load_module(HERE / "lengths" / f"{spec.get('dist', 'uniform')}.py")
    return mod.draw(spec, n, g)


def _output_lengths(mix: list, n: int, g: np.random.Generator):
    shares = np.array([m["share"] for m in mix], np.float64)
    if abs(shares.sum() - 1.0) > 1e-9:
        raise ValueError(f"output shares sum to {shares.sum()}, not 1")
    counts = np.floor(shares * n).astype(int)
    counts[0] += n - counts.sum()
    out = np.concatenate([_lengths(m, c, g) for m, c in zip(mix, counts)])
    return g.permutation(out)


def schedule(traffic: dict, seconds: float, seed: int, vocab_size: int,
             rate_per_s: Optional[float] = None) -> List[Request]:
    """The requests due in ``[0, seconds)``, sorted by due time."""
    g = rng(traffic["shape_seed"])
    arr = traffic["arrivals"]
    proc = load_module(HERE / "arrivals" / f"{arr['process']}.py")
    due, group = proc.draw(arr, rate(traffic) if rate_per_s is None
                           else float(rate_per_s), seconds, g)
    n = len(due)
    plen = _lengths(traffic["prompt"], n, g)
    olen = _output_lengths(traffic["output"], n, g)
    pods = traffic.get("pods")
    pod_of = np.full(n, -1)
    if pods:
        if group is None:
            group = np.arange(n)
        groups = np.unique(group)
        pod_by_group = dict(zip(groups, g.integers(0, int(pods["count"]),
                                                   len(groups))))
        pod_of = np.array([pod_by_group[b] for b in group])
    run = rng(seed)
    preambles = [run.integers(1, vocab_size, int(pods["preamble_tokens"]),
                              dtype=np.int32)
                 for _ in range(int(pods["count"]))] if pods else []
    out = []
    for i in range(n):
        suffix = run.integers(1, vocab_size, int(plen[i]), dtype=np.int32)
        if pod_of[i] >= 0:
            pre = preambles[pod_of[i]]
            prompt, shared = np.concatenate([pre, suffix]), len(pre)
        else:
            prompt, shared = suffix, 0
        out.append(Request(rid=i, due_s=float(due[i]), prompt=prompt,
                           max_new_tokens=int(olen[i]), pod=int(pod_of[i]),
                           shared_tokens=shared))
    return [r for r in out if r.due_s < seconds]


def max_request_tokens(traffic: dict) -> int:
    """Largest prompt plus output a request of this mix can have."""
    pre = int(traffic["pods"]["preamble_tokens"]) if traffic.get("pods") else 0
    return pre + int(traffic["prompt"]["max"]) + max(
        int(m["max"]) for m in traffic["output"])
