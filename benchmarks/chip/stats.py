"""Arithmetic on host-clock stamps: percentiles, quartile spread, slopes."""
from __future__ import annotations

import math
import statistics
from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float | None:
    """The ``q``-th percentile (0-100) of ``values`` by linear interpolation
    between order statistics (numpy's default); None when there are none."""
    v = np.asarray(values, dtype=np.float64)
    v = v[np.isfinite(v)]
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def slope(ts: Sequence[float], ys: Sequence[float]) -> float | None:
    """Least-squares slope of ``ys`` over ``ts``; None with fewer than two
    distinct times."""
    t = np.asarray(ts, dtype=np.float64)
    y = np.asarray(ys, dtype=np.float64)
    if t.size < 2 or float(np.ptp(t)) == 0.0:
        return None
    tc = t - t.mean()
    return float(np.dot(tc, y - y.mean()) / np.dot(tc, tc))


def union_length(intervals: Sequence[tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, -math.inf, -math.inf
    for s, e in sorted(intervals):
        if s > cur_e:
            if cur_e > cur_s:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e > cur_s:
        total += cur_e - cur_s
    return total
