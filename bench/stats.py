"""Arithmetic of the end-to-end metrics: percentiles and rates over every
request retired in the window."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float | None:
    """Nearest-rank percentile: the smallest value that at least ``q``
    percent of ``values`` do not exceed; ``None`` for no values."""
    if not values:
        return None
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(q / 100 * len(ordered))) - 1]


def rate(count: int, seconds: float) -> float | None:
    """Events per second over a window; ``None`` for an empty window."""
    if seconds <= 0 or count <= 0:
        return None
    return count / seconds
