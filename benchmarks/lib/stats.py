"""Order statistics, as the benchmark reports them."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, as numpy's default; NaN for no values."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def share_over_pct(values: Sequence[float], times_median: float) -> float:
    """The share (0..100) of ``values`` above ``times_median`` medians; 0 for
    no values.  Of a serving window's token gaps at 2 medians: the class of
    gap that carries a prefill unit beside the decode step."""
    if not values:
        return 0.0
    edge = times_median * median(values)
    return 100.0 * sum(1 for v in values if v > edge) / len(values)
