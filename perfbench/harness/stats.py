"""The arithmetic of the end-to-end metrics, in one place."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0–100) by linear interpolation between
    closest ranks (numpy's default): rank ``q/100 · (n − 1)`` of the sorted
    values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def rate(count: int, seconds: float) -> float:
    """Events per second over a window."""
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds

