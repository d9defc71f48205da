"""Profiling hooks: ``jax.profiler`` capture, spans on the profiler's
clock, and static kernel cost annotations.

Three pieces:

  * :func:`span` — a named host span recorded by ``jax.profiler`` on the
    same clock as the device planes of a capture (the serving scheduler
    marks the phases of each step with it). Outside a capture it costs
    about a microsecond and records nothing; there is no other switch.
  * :func:`profiled` — a context manager wrapping the jitted hot loop in
    a ``jax.profiler`` trace when a capture directory is set (view the
    result in TensorBoard / Perfetto). Zero-cost no-op when disabled; a
    requested capture that the profiler cannot take raises.
  * :func:`kernel_cost_args` — static per-kernel cost annotations for
    span ``args``: padded tokens and attention MACs priced through the
    same :class:`repro.serve.loadgen.PrefillCostModel` accounting the
    serving tier's sim clock uses. On an interpret-mode CPU container
    the Pallas wall-clock says nothing about accelerator cost; the MAC
    model is the honest FLOP proxy, so traces carry it on every compute
    span instead of pretending host time is device time.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    """``with span("scheduler.decode"): ...`` records a wall-clock span
    named ``name`` in a running ``jax.profiler`` capture (it lands in
    the capture's ``.xplane.pb``, host plane, on the clock of the device
    planes). With no capture running nothing is recorded."""
    return TraceAnnotation(name)


@dataclasses.dataclass(frozen=True)
class ProfileOptions:
    """Where (and whether) to capture a ``jax.profiler`` trace.

    ``jax_trace_dir=None`` disables capture entirely — the context
    manager is then a no-op and the traced run stays bit-identical."""

    jax_trace_dir: Optional[str] = None
    create_perfetto_link: bool = False


@contextlib.contextmanager
def profiled(options: Optional[ProfileOptions] = None):
    """Wrap a block in ``jax.profiler.trace`` when enabled.

    Usage::

        with profiled(ProfileOptions(jax_trace_dir="/tmp/jaxtrace")):
            out = session.run(steps)
    """
    if options is None or options.jax_trace_dir is None:
        yield
        return
    import jax.profiler as _prof
    with _prof.trace(options.jax_trace_dir,
                     create_perfetto_link=options.create_perfetto_link):
        yield


def kernel_cost_args(*, padded_tokens: int = 0, attn_mac: int = 0,
                     flops: float = 0.0, cost_model=None) -> Dict:
    """Static cost annotation dict for a span's ``args``.

    ``padded_tokens`` / ``attn_mac`` follow the scheduler's
    ``last_stats`` accounting (linear work per padded token + attention
    score MACs); ``flops`` is the FL compute model's per-round estimate.
    When a :class:`repro.serve.loadgen.PrefillCostModel` (anything with
    ``step_cost``) is given, the modeled seconds ride along as
    ``est_cost_s`` — the exact surcharge the sim clock charged."""
    args: Dict = {}
    if padded_tokens:
        args["padded_tokens"] = int(padded_tokens)
    if attn_mac:
        args["attn_mac"] = int(attn_mac)
    if flops:
        args["flops"] = float(flops)
    if cost_model is not None and (padded_tokens or attn_mac):
        args["est_cost_s"] = float(cost_model.step_cost(
            {"prefill_padded_tokens": padded_tokens,
             "prefill_attn_mac": attn_mac}))
    return args
