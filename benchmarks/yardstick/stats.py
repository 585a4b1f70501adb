"""Percentile and rate arithmetic, kept with the benchmark so that no
later PR can change how a number is computed."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default 'linear' rule),
    ``q`` in [0, 100]. None for an empty sample."""
    if not values:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def sliced_percentiles(rows: Iterable[tuple[float, float]], width: float,
                       seconds: float, q: float) -> list[float]:
    """Percentile ``q`` of every whole slice of ``width`` seconds of a
    window of ``seconds``: ``rows`` are (seconds into the window, value).
    A tail is one number a stall, and which stalls a window catches
    swings it; the median of the slices' percentiles is what the tail is
    in the window's middle slice, which no single stall moves. Rows
    behind the last whole slice belong to none; an empty slice is left
    out."""
    n = int(seconds / width + 1e-9)
    slices: list[list[float]] = [[] for _ in range(n)]
    for t, value in rows:
        k = int(t // width)
        if 0 <= k < n:
            slices[k].append(value)
    return [percentile(s, q) for s in slices if s]


def samples_beyond(n: int, q: float) -> float:
    """How many samples lie beyond percentile ``q`` in a sample of n: a
    percentile is worth reporting with at least ten beyond it."""
    return n * (100.0 - q) / 100.0


def rate(count: float, seconds: float) -> Optional[float]:
    if seconds <= 0:
        return None
    return count / seconds


def share_pct(part: float, whole: float) -> Optional[float]:
    if whole <= 0:
        return None
    return 100.0 * part / whole


def spread(values: Sequence[float]) -> Optional[float]:
    """The driver's spread: distance between the quartiles over the
    median."""
    if len(values) < 2:
        return None
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(med)


def union_seconds(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
