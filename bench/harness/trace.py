"""Capture a profiler trace of part of the window and reduce it.

The reduction works on a plain form, :class:`Trace`: device operation
intervals per chip ("XLA Ops" lines of the ``/device:TPU:<n>`` planes),
program launches per chip ("XLA Modules"), and the benchmark's own host
spans (``jax.profiler.TraceAnnotation`` names). From it:

* busy time: the union of a chip's operation intervals inside the traced
  window; idle is the rest of the window;
* idle gaps, each attributed to the innermost host span that covers its
  middle (what the host was doing while the chip waited);
* a kernel's device time: the summed durations of the operations whose
  name matches it;
* a program's device time per launch, from its module events;
* the operations that took most device time, by self time (an operation
  that encloses others on its line, such as a loop, keeps only the time
  not covered by them).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
import shutil
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: the span that brackets the traced part of the window
WINDOW_SPAN = "bench.window"
#: host spans the benchmark itself records (prefixes)
HOST_PREFIXES = ("bench.", "driver.", "scheduler.", "engine.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[str, List[Event]]          # device plane -> operations
    modules: Dict[str, List[Event]]      # device plane -> program launches
    host: List[Event]                    # benchmark host spans

    # ---- (de)serialisation for recorded test traces --------------------
    def to_json(self) -> dict:
        ev = lambda es: [[e.name, e.start, e.end] for e in es]
        return {"window": list(self.window),
                "ops": {k: ev(v) for k, v in self.ops.items()},
                "modules": {k: ev(v) for k, v in self.modules.items()},
                "host": ev(self.host)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda rows: [Event(n, float(s), float(e)) for n, s, e in rows]
        return cls(tuple(d["window"]),
                   {k: ev(v) for k, v in d["ops"].items()},
                   {k: ev(v) for k, v in d["modules"].items()},
                   ev(d["host"]))


# ---------------------------------------------------------------- read ----
def load_xplane(path: Path) -> Trace:
    """Read a profiler ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, host = defaultdict(list), defaultdict(list), []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                dest[plane.name].extend(
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                    elif e.name.startswith(HOST_PREFIXES):
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns)
                                          * 1e-9))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    return Trace(window, dict(ops), dict(modules), host)


def survey(path: Path, top: int = 12) -> dict:
    """Planes, lines and their most frequent event names: what a reader
    of the trace needs to know to match kernels by name."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            tot = defaultdict(float)
            for e in line.events:
                tot[e.name] += e.duration_ns * 1e-9
            lines[line.name] = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        out[plane.name] = lines
    return out


# -------------------------------------------------------------- reduce ----
def _clip(events: List[Event], window) -> List[Tuple[float, float]]:
    lo, hi = window
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(tr: Trace, plane: str) -> float:
    return sum(e - s for s, e in union(_clip(tr.ops.get(plane, []),
                                             tr.window)))


def mean_busy_seconds(tr: Trace) -> float:
    """Busy seconds averaged over the chips in the trace."""
    planes = sorted(tr.ops)
    if not planes:
        return 0.0
    return sum(busy_seconds(tr, p) for p in planes) / len(planes)


def idle_gaps(tr: Trace, plane: str) -> List[Tuple[float, float]]:
    lo, hi = tr.window
    gaps, t = [], lo
    for s, e in union(_clip(tr.ops.get(plane, []), tr.window)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(gaps, host: List[Event]) -> Dict[str, float]:
    """Idle seconds by the innermost host span covering each gap's middle
    (``(no span)`` where the benchmark recorded none)."""
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [h for h in host if h.start <= mid < h.end]
        name = min(cover, key=lambda h: h.dur).name if cover else "(no span)"
        out[name] += e - s
    return dict(out)


def matching(events: List[Event], pattern: str, window) -> List[Event]:
    rx = re.compile(pattern)
    lo, hi = window
    return [e for e in events if rx.search(e.name) and lo <= e.start < hi]


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds per operation name, each event less the time of the events
    it encloses on the same line."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []         # [event, child seconds]

    def close(item):
        ev, child = item
        out[ev.name] += max(ev.dur - child, 0.0)
        if stack:
            stack[-1][1] += ev.dur

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= ev.start:
            close(stack.pop())
        stack.append([ev, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


_HLO = re.compile(r"%?([\w.-]+) = (\S+?)(?:\{[^ ]*\})? ([\w-]+)\(")


def short_name(name: str) -> str:
    """``%copy.67 = bf16[8,2048,16,64]{3,2,1,0:T(8,128)} copy(...)`` ->
    ``copy.67 bf16[8,2048,16,64] copy``: the TPU trace names an operation
    by its whole HLO text."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)} {m.group(3)}" if m else name


def top_ops(tr: Trace, plane: str, n: int = 10) -> List[List]:
    lo, hi = tr.window
    ev = [Event(short_name(e.name), max(e.start, lo), min(e.end, hi))
          for e in tr.ops.get(plane, []) if e.end > lo and e.start < hi]
    st = self_times(ev)
    return [[k, v] for k, v in sorted(st.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(tr: Trace, n: int = 10) -> dict:
    plane = sorted(tr.ops)[0] if tr.ops else None
    if plane is None:
        return {"device_ops": [], "idle_gaps": []}
    gaps = attribute(idle_gaps(tr, plane), tr.host)
    return {"device_ops": top_ops(tr, plane, n),
            "idle_gaps": [[k, v] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:n]]}


# ------------------------------------------------------------- capture ----
class Capture:
    """``jax.profiler`` trace of a stretch of the window, written under a
    fixed directory that is emptied first. ``start_s`` and ``stop_s``
    are the host time that starting and stopping it took inside the
    window."""

    def __init__(self, out_dir: Path):
        self.dir = Path(out_dir)
        self.started = False
        self.start_s = self.stop_s = 0.0

    def start(self) -> None:
        import jax

        t = time.perf_counter()
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.started = True
        self.start_s = time.perf_counter() - t

    def stop(self) -> None:
        import jax

        t = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t

    def load(self) -> Trace:
        files = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        path = Path(files[-1])
        (self.dir / "survey.json").write_text(json.dumps(survey(path),
                                                         indent=1))
        return load_xplane(path)
