"""The benchmark's arithmetic of percentiles and rates.

``percentile`` is a copy of ``repro.obs.metrics.percentile`` (linear
interpolation between closest ranks, numpy's default), kept here so that
a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (q in [0, 100]); empty input is an error."""
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        raise ValueError("percentile of no values")
    if n == 1:
        return float(xs[0])
    pos = (q / 100.0) * (n - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def mean(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("mean of no values")
    return float(sum(values)) / len(values)


def rate(count: float, seconds: float) -> float:
    """A count over the whole of a window."""
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds
