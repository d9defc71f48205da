"""The host's wait for sampled tokens per scheduler step in the traced
stretch: over the steps ``host_step_ms.serve`` counts (those whose
``scheduler.admit`` span starts in the traced window), the summed
durations of the step's ``scheduler.tokens`` spans (a prefill's first
token, the decode's tokens), averaged per step. None where the trace
holds no ``scheduler.admit`` span."""
from bench.harness.loader import metric_reader

TOKENS = ("scheduler.tokens",)


def read(data):
    return metric_reader("host_step_ms.serve").mean_ms(data.trace, TOKENS)
