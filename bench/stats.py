"""Percentiles over raw client stamps.

A percentile here is the nearest-rank value of the sorted samples: the
smallest sample with at least ``q`` percent of the samples at or below it.
A missing sample (a request that failed or never produced a token) is
``inf`` and sorts last, so it counts against the tail rather than being
dropped.
"""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    vals = sorted(values)
    if not vals:
        return math.inf
    k = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[k - 1]


def ttfts(records) -> list[float]:
    """Seconds from each request's due time to its first token at the
    client; ``inf`` where none came."""
    return [r.stamps[0] - r.due if r.stamps else math.inf for r in records]


def token_gaps(records) -> list[float]:
    """Every gap between consecutive tokens of a request, all requests."""
    out = []
    for r in records:
        out.extend(b - a for a, b in zip(r.stamps, r.stamps[1:]))
    return out
