"""repro.obs — unified observability for the FL engine and serving tier.

Three pieces, all host-side and all zero-cost when disabled. They keep
two clocks apart:

  * :mod:`repro.obs.trace` — a span tracer emitting Chrome trace-event /
    Perfetto-compatible JSON on the **caller's** clock (the simulated
    clock of the loadgen and the FL fabric, or whatever ``t`` a driver
    passes to ``ContinuousScheduler.step``), with one track
    per vehicle / edge / cloud (FL fabric) and per serving lane
    (continuous scheduler). ``tracer=None`` everywhere means no
    callbacks fire: event logs, params, and greedy streams are bitwise
    identical to an untraced run (enforced by ``tests/test_obs.py``).
  * :mod:`repro.obs.metrics` — a registry of labeled counters / gauges /
    histograms (uplink/backhaul bytes, observed staleness, block-pool
    occupancy + high-watermark, draft acceptance, preemptions)
    that the train loops, the event engine, and the continuous scheduler
    publish into, snapshotting to JSON.
  * :mod:`repro.obs.profile` — the **profiler's** clock: optional
    ``jax.profiler`` trace capture around jitted steps, and
    :func:`span`, the wall-clock host spans the serving scheduler puts
    around the phases of each step (``scheduler.admit``,
    ``scheduler.prefill``, ``scheduler.decode``, ``scheduler.tokens``,
    ``scheduler.commit``), recorded in the same ``.xplane.pb`` as the
    device planes whenever a capture is running; plus static per-kernel
    cost annotations (the :class:`repro.serve.PrefillCostModel` MAC
    accounting) attached to Tracer spans.

Capture points: ``Session.run(trace=...)`` / ``Session.serve(trace=...)``
and the ``--trace PATH`` flags on ``launch/train.py``,
``launch/serve.py`` and ``launch/dryrun.py``. Validate any emitted file
with ``scripts/validate_trace.py`` and open it at https://ui.perfetto.dev
or ``chrome://tracing``.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.profile import (ProfileOptions, kernel_cost_args, profiled,
                               span)
from repro.obs.trace import (FL_PID, SERVE_PID, TRACE_SCHEMA, Tracer,
                             resolve_tracer)

__all__ = ["Counter", "FL_PID", "Gauge", "Histogram", "MetricsRegistry",
           "ProfileOptions", "SERVE_PID", "TRACE_SCHEMA", "Tracer",
           "kernel_cost_args", "profiled", "resolve_tracer", "span"]
