"""Percentiles and the open-loop latency arithmetic.

Every request of an open loop is timed from when it was *due*, not from
when the generator got round to submitting it, so a stall charges every
request queued behind it. A request that failed or never finished counts
as infinitely late: it sits at the top of every percentile.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100). Infinite entries
    are ordinary values here, so a tail that reaches a failure is
    infinite."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def latency_from_due(due_s, done_s, ok) -> np.ndarray:
    """Per-request latency in seconds: completion minus due time, or
    infinity where the request failed or has no completion time."""
    due = np.asarray(due_s, np.float64)
    done = np.asarray([np.nan if d is None else d for d in done_s], np.float64)
    good = np.asarray(ok, bool) & np.isfinite(done)
    return np.where(good, done - due, np.inf)
