"""The arithmetic from samples to the numbers a run reports."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule), q in [0, 100].
    An empty sample has no percentile: callers decide what that means."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def percentile_or(values, q: float, empty: float) -> float:
    values = list(values)
    return percentile(values, q) if values else empty


def median(values) -> float:
    return percentile(values, 50.0)
