"""Percentiles and spreads over all samples of a window.

``percentile`` is a copy of the nearest-rank definition of the program's
``repro/serve/trace.py`` (``_rank``: the sample at rank ``ceil(p/100 *
n)``), so a tail is always a latency some request had."""
from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(0, min(len(xs) - 1, math.ceil(p / 100.0 * len(xs)) - 1))
    return float(xs[rank])


def median(values) -> float:
    return float(statistics.median(values))


def spread(values) -> float:
    """Interquartile distance as a share of the median (Python's
    ``statistics.quantiles``, exclusive method)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
