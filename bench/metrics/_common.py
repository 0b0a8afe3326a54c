"""Helpers the metric readers share (no metric of its own)."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: the smallest value with at least
    a share ``q`` of the values at or below it (``inf`` counts as a value)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]
