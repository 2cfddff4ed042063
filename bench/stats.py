"""Percentiles, spreads and window arithmetic of the benchmark."""

from __future__ import annotations

import math
import statistics

__all__ = ["percentile", "spread", "union_length", "clip_intervals", "gaps"]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0 < q <= 100) by the nearest rank: the
    smallest value with at least q% of the sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def spread(values) -> float:
    """Distance between the first and third quartile over the median, as
    ``statistics.quantiles(values, n=4)`` gives them."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def clip_intervals(intervals, lo: float, hi: float):
    """The parts of ``[(start, end), ...]`` that fall inside ``[lo, hi]``."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union_length(intervals) -> float:
    """Length of the union of ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The idle stretches of ``[lo, hi]`` that no interval covers, as
    ``[(start, end), ...]`` in time order."""
    out, t = [], lo
    for s, e in sorted(clip_intervals(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out
