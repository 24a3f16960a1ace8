"""Statistics on raw samples (no histogram buckets)."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile: the smallest sample with at
    least ``q`` percent of the samples at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return v[rank - 1]

