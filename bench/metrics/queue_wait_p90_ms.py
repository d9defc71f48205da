"""90th percentile of the wait from a request's due time to the step
that admitted it to a lane (the scheduler's ``t_admit``, which the
driver sets to the step's wall time), over the window's requests."""
from bench.harness.stats import percentile


def read(data):
    waits = [r.admit - r.due for r in data.window.recs.values()
             if r.admit is not None]
    return percentile(waits, 90) * 1e3 if waits else None
