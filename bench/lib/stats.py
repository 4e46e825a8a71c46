"""Percentile arithmetic of the benchmark's own."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default method)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    r = (len(xs) - 1) * p / 100.0
    lo = math.floor(r)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)


def median(values) -> float:
    return percentile(values, 50.0)
