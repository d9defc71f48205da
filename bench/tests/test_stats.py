import numpy as np
import pytest

from bench.harness.stats import percentile


def test_percentile_uses_every_sample():
    v = list(range(1, 101))             # 1..100
    assert percentile(v, 90) == pytest.approx(90.1)
    assert percentile(v, 50) == pytest.approx(50.5)
    assert percentile([7.0], 95) == 7.0
    # one far tail sample moves the p95 of 20 samples
    base = [1.0] * 19
    assert percentile(base + [100.0], 95) > percentile(base + [1.0], 95)


def test_percentile_matches_numpy_linear():
    x = np.random.default_rng(0).exponential(size=257)
    for q in (50, 90, 95, 99):
        assert percentile(x, q) == pytest.approx(np.percentile(x, q))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        percentile([], 90)
