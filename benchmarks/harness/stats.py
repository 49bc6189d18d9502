"""Small statistics the harness and its tests share."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Exact nearest-rank percentile, q in (0, 1]: no interpolation, so the
    value is one that was observed (copied from scripts/loadgen.py `_pctl`,
    without its rounding)."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's `statistics.quantiles(n=4)`: the driver's rule."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))
