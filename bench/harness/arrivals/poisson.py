"""Poisson arrivals: ``round(rate * seconds)`` due times placed uniformly
at random in the window (a Poisson process conditioned on its count).
No parameters beyond the mix's rate."""
import numpy as np


def draw(spec: dict, rate: float, seconds: float, g: np.random.Generator):
    """(sorted due times, group per request or None)."""
    n = max(1, int(round(rate * seconds)))
    return np.sort(g.uniform(0.0, seconds, n)), None
