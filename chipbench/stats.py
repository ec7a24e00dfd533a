"""Latency arithmetic."""
from __future__ import annotations

import math
from typing import Dict, Sequence


def tail(values: Sequence[float], q: float = 0.95) -> Dict[str, float]:
    """Nearest-rank ``q`` quantile of ``values``, with the sample count and
    how many samples lie beyond it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    rank = max(1, math.ceil(q * len(xs)))
    return dict(value=xs[rank - 1], n=len(xs), beyond=len(xs) - rank)


def mean(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("no samples")
    return math.fsum(values) / len(values)

