"""Order statistics, one definition for the whole benchmark."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between the
    two nearest order statistics (numpy's default). A value of `inf` (a
    request that failed or was refused) sorts last and is returned as it is."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    pos = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(ordered[hi]):
        return ordered[hi]
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)
