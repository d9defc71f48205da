"""One run of one cell: set-up, window, metrics, check, line.

The cell's traffic mix names its driver, ``drivers/<driver>.py``, whose
``Driver`` class does everything that depends on what the cell runs:

* ``Driver(cell, seed, mark, patch)``: set-up (the program, its weights
  or state from the seed, every program of the window warmed);
  ``mark(what)`` logs a set-up phase, ``patch(drv)`` lets a test break
  the timed path underneath;
* ``work(seconds)``: the window's inputs, from the seed;
* ``window(work, seconds, compile_log, capture)``: the timed window,
  starting and stopping ``capture`` (a profiler trace) inside it;
* ``release()``: frees the program's state before the check;
* ``check(w, control=False)``: the numbers compared, with their limits;
* ``end_to_end(w)``, ``layer_data(w)`` (what the per-layer readers
  read), ``attempted(w)``, ``failed(w)``, ``summary(w)``.

``bench/run.py`` calls :func:`run`; ``bench/sweep.py`` and
``bench/readings.py`` drive a ``Driver`` over several rates or seeds.
"""
from __future__ import annotations

import gc
import time
import types
from typing import Optional

from bench.harness import device
from bench.harness.loader import BENCH, ROOT, Cell, metric_reader

CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = BENCH / ".out"


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the fixed path in this
    checkout; every program is kept, also the quick ones."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def marker(t_start: float, devices):
    """``mark(what)``: logs the seconds since the process started and the
    chips' memory in use and at peak so far."""
    def mark(what: str) -> None:
        log(f"{what}: {time.perf_counter() - t_start:.3f} s"
            f"{device.memory_note(devices)}")
    return mark


def is_correct(checks: dict) -> bool:
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        cell: Optional[Cell] = None, patch=None, peak=None):
    """One run; returns (result dict, checks). ``patch`` goes to the
    driver; ``peak`` stands in for the peaks table where a test runs off
    the chip."""
    import jax

    from bench.harness.compile_log import CompileLog

    cell = cell or Cell(cell_name)
    if require_chip:
        devices = device.require_chips(cell.chips)
        use_compile_cache()
    else:
        devices = jax.devices()[:cell.chips]
    clog = CompileLog()
    mark = marker(t_start, devices)
    mark("chips found")
    drv = cell.driver().Driver(cell, seed, mark=mark, patch=patch)
    work = drv.work(seconds)
    capture = None
    if trace:
        from bench.harness.trace import Capture
        capture = Capture(OUT_DIR / cell.name / "trace")
    setup_compile_s, setup_compiles, hits = clog.snapshot()
    setup_s = time.perf_counter() - t_start
    mark("set-up done")
    gc.collect()
    gc.freeze()                 # set-up's objects: out of every collection
    w = drv.window(work, seconds, clog, capture)
    mem = device.memory_peak_bytes(devices)
    mark("window done")
    drv.release()
    log(f"set-up {setup_s:.3f} s, {setup_compiles} backend compiles "
        f"{setup_compile_s:.3f} s, {hits} persistent-cache hits; window "
        + drv.summary(w) + (f"; the profiler's start stalled the host "
                            f"{capture.start_s:.3f} s, its stop "
                            f"{capture.stop_s:.3f} s" if trace else ""))
    t_check = time.perf_counter()
    checks = drv.check(w)
    log(f"check took {time.perf_counter() - t_check:.1f} s")

    info = device.describe(devices)
    info["memory_peak_bytes"] = mem
    metrics = {}
    if not trace:
        e2e = drv.end_to_end(w)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench.harness import trace as TR
        tr = capture.load()
        info["busy_s"] = TR.mean_busy_seconds(tr)
        info["window_s"] = tr.window[1] - tr.window[0]
        data = types.SimpleNamespace(
            peak=peak or device.peaks(info["kind"]), trace=tr,
            chips=len(devices), setup_compile_s=setup_compile_s,
            **drv.layer_data(w))
        for m in cell.per_layer():
            v = metric_reader(m["name"]).read(data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": is_correct(checks), "attempted": drv.attempted(w),
              "failed": drv.failed(w), "metrics": metrics, "device": info}
    if trace:
        result["breakdown"] = TR.breakdown(tr)
    result["checks"] = checks
    return result, checks


def check_lines(checks: dict) -> list:
    return [f"check {k} {v['value']!r} limit {v['limit']!r}"
            for k, v in checks.items()]
