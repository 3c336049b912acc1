"""Arithmetic over a measured window: tails, counts, interval unions.

Every function here is plain Python over host-clock or trace-clock
numbers, so the tests check it without a chip.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it.  Always one of the values, so
    a tail is a request that happened, not an interpolation."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of closed intervals, as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e < s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Tuple[float, float]], w0: float, w1: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def union_length(intervals: Iterable[Tuple[float, float]], w0: float,
                 w1: float) -> float:
    """Length of the union of ``intervals`` inside [w0, w1]."""
    return sum(e - s for s, e in merge(clip(intervals, w0, w1)))


def gaps(intervals: Iterable[Tuple[float, float]], w0: float, w1: float
         ) -> List[Tuple[float, float]]:
    """The parts of [w0, w1] that no interval covers."""
    out, t = [], w0
    for s, e in merge(clip(intervals, w0, w1)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out
