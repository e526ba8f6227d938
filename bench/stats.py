"""Percentiles, kept with the benchmark so no later change to the program
can move them: the linear interpolation between closest ranks that
``numpy.percentile`` uses by default, as the scheduler's report computes
its ``p50/p99_*`` columns.
"""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """The ``p``-th percentile (0..100) of ``values``, linearly interpolated
    between the two closest ranks. Raises on an empty sequence."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile {p} outside 0..100")
    rank = (len(xs) - 1) * p / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)

