"""Percentile and spread arithmetic, in one place."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile (0 < p <= 100) by the nearest-rank rule: the
    smallest value with at least p% of the samples at or below it.  No
    interpolation, so the result is always a value that was observed and
    an infinite sample (a failed request) stays infinite only when the
    rank reaches it."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    s = sorted(values)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def median(values: Sequence[float]) -> float:
    return statistics.median(values)


def iqr_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median with `statistics.quantiles(values, n=4)`: the
    spread the bounds are set from."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
