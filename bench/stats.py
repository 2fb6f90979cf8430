"""Percentiles and spreads, kept with the benchmark so that no change to the
program can move them."""
from __future__ import annotations

import math
import statistics


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile: the smallest element with at least ``q`` of
    the sample at or below it (1-based rank ``ceil(q * n)``). ``inf`` entries
    stand for requests that failed, and sort last. Empty: ``nan``."""
    xs = sorted(xs)
    n = len(xs)
    if n == 0:
        return math.nan
    i = min(n - 1, max(0, math.ceil(q * n) - 1))
    return xs[i]


def spread(values) -> float:
    """Interquartile distance over the median, from Python's
    ``statistics.quantiles(values, n=4)`` (the benchmark's rule for bounds)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
