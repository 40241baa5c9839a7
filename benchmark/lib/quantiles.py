"""Quantiles over raw samples (no buckets): linear interpolation between
order statistics, the rule numpy calls ``linear``."""
from __future__ import annotations

from typing import Sequence


def quantile(samples: Sequence[float], q: float) -> float:
    if not samples:
        raise ValueError("no samples")
    if not 0.0 <= q <= 1.0:
        raise ValueError("q must lie in [0, 1]")
    ordered = sorted(samples)
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q`` quantile."""
    return int(n * (1.0 - q))
