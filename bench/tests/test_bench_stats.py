"""Percentiles: the benchmark's own arithmetic."""
import numpy as np
import pytest

from bench.lib.stats import median, percentile


@pytest.mark.parametrize("p", [0, 5, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(p):
    xs = np.random.default_rng(p).exponential(100.0, 337).tolist()
    assert percentile(xs, p) == pytest.approx(np.percentile(xs, p), rel=1e-12)


def test_median_of_even_count_interpolates():
    assert median([4.0, 1.0, 3.0, 2.0]) == pytest.approx(2.5)
    with pytest.raises(ValueError):
        median([])
