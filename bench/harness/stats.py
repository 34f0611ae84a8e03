"""Percentile and rate arithmetic (percentile rule copied from
bench_serve.py `_pct`: the value at index int(n*q) of the sorted sample,
which is the smallest value with at least a share q of the sample
strictly below or at it)."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def pct(values: Iterable[float], q: float) -> Optional[float]:
    s = sorted(values)
    if not s:
        return None
    return s[min(len(s) - 1, int(len(s) * q))]


def median(values: Iterable[float]) -> Optional[float]:
    return pct(values, 0.5)


def mean(values: Iterable[float]) -> Optional[float]:
    v = list(values)
    return sum(v) / len(v) if v else None


def censored(latencies: List[Optional[float]], never: float) -> List[float]:
    """A failed request misses every latency: it enters the percentiles
    at `never` (the time the client gave up waiting), which stands for
    +inf and is above any latency a finished request can have."""
    return [never if (x is None or not math.isfinite(x)) else x
            for x in latencies]


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
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
