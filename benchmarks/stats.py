"""Small statistics the harness and the reducers share."""
from __future__ import annotations

import math


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least a share q
    of the sample at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("quantile of an empty sample")
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]
