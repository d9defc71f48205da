"""Uniform whole lengths in [``min``, ``max``] tokens."""
import numpy as np


def draw(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    return g.integers(int(spec["min"]), int(spec["max"]) + 1, n
                      ).astype(np.int64)
