"""Percentiles, failures and rates, computed the same way in every run."""
from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) with linear interpolation between
    the two nearest ranks; ``values`` must be non-empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies(due: Sequence[float], done: Sequence[Optional[float]],
              deadline: float) -> tuple:
    """Latency of each request that completed by ``deadline`` (completion
    minus due time) and the number that did not (``None`` or later than
    ``deadline``): a request that never came back, or came back after the
    drain limit, is a failed request and has no latency."""
    lat: List[float] = []
    failed = 0
    for d, c in zip(due, done):
        if c is None or c > deadline:
            failed += 1
        else:
            lat.append(c - d)
    return lat, failed


def rate(work: float, t0: float, t1: float) -> float:
    """Work per second over the whole interval [t0, t1]."""
    if t1 <= t0:
        raise ValueError(f"empty interval [{t0}, {t1}]")
    return work / (t1 - t0)

