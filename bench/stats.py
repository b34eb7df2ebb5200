"""Percentiles and rates over a measured window."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank ``p``-th percentile: the smallest value with at least
    ``p`` percent of the values at or below it.  It is always one of the
    values, so a tail is a latency some request really had."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    return xs[max(0, math.ceil(p / 100.0 * len(xs)) - 1)]


def completed_in(records, t0: float, t1: float) -> list:
    """Records whose result came back inside [t0, t1]."""
    return [r for r in records
            if r.done_t is not None and r.ok and t0 <= r.done_t <= t1]


def drain_end(records, t1: float) -> float:
    """The close of a window that waits for its work: the clock when the
    last request submitted in the window came back, or ``t1`` where none
    came back later."""
    return max([t1] + [r.done_t for r in records if r.done_t is not None])


def rate(amounts, seconds: float) -> float:
    """Work per second: all of the work, divided by all of the time it
    took, not by the span of its completions."""
    if seconds <= 0:
        raise ValueError("window of no length")
    return float(sum(amounts)) / seconds
