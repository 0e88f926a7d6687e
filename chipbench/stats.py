"""The little arithmetic the harness needs."""

from __future__ import annotations

import math


def percentile(values: list[float], p: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least ``p``
    percent of the values at or under it."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(p / 100.0 * len(ordered)) - 1)]
