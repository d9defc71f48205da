"""Lognormal lengths: ``median`` and ``sigma`` of the log, rounded and
clipped to [``min``, ``max``] tokens."""
import numpy as np


def draw(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * g.standard_normal(n))
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])
                   ).astype(np.int64)
