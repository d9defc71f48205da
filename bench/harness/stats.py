"""Percentiles over every sample (no sub-sampling, no trimming)."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all ``values``, linear between order
    statistics (numpy's default, Hyndman-Fan type 7). Raises on no data:
    a tail of nothing is not 0."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(v, q))
