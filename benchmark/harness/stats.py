"""Metric arithmetic: rates over the whole window, tails over all requests."""
from __future__ import annotations

import math


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of all the values given: the
    smallest value with at least q% of the sample at or below it. None for
    an empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[k - 1]


def median(values):
    vals = sorted(values)
    if not vals:
        return None
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])


def rate(work, seconds):
    """All the work of the window over all the time of the window."""
    return work / seconds if seconds > 0 else None


def tail_with_misses(latencies, n_missing, q):
    """Tail over ALL requests due: one that failed or never answered counts
    as slower than any that answered (infinite), so it is the tail once
    misses pass 100-q percent."""
    vals = list(latencies) + [math.inf] * int(n_missing)
    return percentile(vals, q)
