"""Percentiles, spelled out so that no library default moves
them."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile over all values (q in 0..100)."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    k = max(0, math.ceil(q / 100.0 * len(s)) - 1)
    return s[k]

