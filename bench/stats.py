"""Exact quantiles (numpy's default linear interpolation, without numpy),
copied from the program's ``obs.metrics.percentile`` so that the benchmark
keeps its own arithmetic."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Exact ``q``-quantile (0 <= q <= 1); None for an empty sample."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q!r}")
    if not values:
        return None
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi:
        return float(s[lo])
    frac = pos - lo
    return float(s[lo] * (1.0 - frac) + s[hi] * frac)
