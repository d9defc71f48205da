"""A Poisson process of bursts: ``round(rate * seconds / mean burst)``
burst starts uniformly at random in the window, each burst sending
``burst_min..burst_max`` requests spread uniformly over ``burst_span_s``.
The mean request rate is the mix's rate; a request's group is its burst."""
import numpy as np


def draw(spec: dict, rate: float, seconds: float, g: np.random.Generator):
    lo, hi = int(spec["burst_min"]), int(spec["burst_max"])
    span = float(spec["burst_span_s"])
    nb = max(1, int(round(rate * seconds / ((lo + hi) / 2.0))))
    starts = np.sort(g.uniform(0.0, max(seconds - span, 0.0), nb))
    sizes = g.integers(lo, hi + 1, nb)
    due = np.concatenate([s + np.sort(g.uniform(0.0, span, k))
                          for s, k in zip(starts, sizes)])
    group = np.repeat(np.arange(nb), sizes)
    keep = np.argsort(due, kind="stable")
    return due[keep], group[keep]
