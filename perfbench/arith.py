"""The benchmark's own arithmetic: percentiles, spreads, arrival
schedules. Kept here so that every PR computes a number the same way."""

from __future__ import annotations

import math
import random


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks of the sorted values (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def poisson_schedule(rate: float, seconds: float, seed: int) -> list[int]:
    """Arrival offsets in nanoseconds from the start, exponential gaps
    at ``rate`` per second, up to ``seconds``. The same seed gives the
    same schedule."""
    rng = random.Random(seed)
    out, t, end = [], 0.0, float(seconds)
    while True:
        t += rng.expovariate(rate)
        if t >= end:
            return out
        out.append(int(t * 1e9))


def union_seconds(intervals) -> float:
    """Seconds covered by the union of (start_ns, duration_ns) pairs."""
    busy, edge = 0, None
    for start, dur in sorted(intervals):
        end = start + dur
        if edge is None or start > edge:
            busy += dur
            edge = end
        elif end > edge:
            busy += end - edge
            edge = end
    return busy / 1e9
