"""Small statistics shared by the metric readers."""
from __future__ import annotations

import math

__all__ = ["percentile"]


def percentile(xs, p: float) -> float:
    """Linear-interpolated percentile (p in [0, 100]), copied from
    `repro.serve.metrics.percentile`; an infinite sample (a request that
    failed) makes every percentile at or above it infinite."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    rank = (p / 100.0) * (len(s) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(s) - 1)
    frac = rank - lo
    if math.isinf(s[lo]) or (frac > 0 and math.isinf(s[hi])):
        return math.inf
    return float(s[lo] * (1.0 - frac) + s[hi] * frac)
