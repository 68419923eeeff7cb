"""Small statistics, one definition each."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by the nearest-rank rule: the
    smallest value with at least q% of the sample at or below it. A value
    that was observed, never an interpolation."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def median(values) -> float:
    return percentile(values, 50.0)
