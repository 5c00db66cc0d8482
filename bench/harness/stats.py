"""Percentiles and spreads, in plain Python."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default);
    ``q`` in [0, 100]. NaN for no values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles(n=4)``
    gives them: the spread a bound is judged against."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
