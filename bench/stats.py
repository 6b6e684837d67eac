"""Order statistics the metric readers share."""

from __future__ import annotations

import math

import numpy as np


def percentile(values, p: float) -> float | None:
    """Nearest-rank ``p``-th percentile; None for no samples or a failure.

    A failed sample is ``inf``: it misses every limit, and a percentile
    that lands on one is not a number.
    """
    vals = np.sort(np.asarray(values, float))
    if vals.size == 0:
        return None
    k = max(0, min(vals.size - 1, math.ceil(p / 100 * vals.size) - 1))
    return float(vals[k]) if np.isfinite(vals[k]) else None
