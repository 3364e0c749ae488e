"""Order statistics over every sample of a run: no chunking, no medians
of medians."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile: the smallest sample with at least
    q% of all samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    return xs[min(len(xs), max(1, math.ceil(q / 100 * len(xs)))) - 1]


def rate(items: int, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError("empty window")
    return items / seconds
