"""Order statistics used by the metrics and the spread calculations."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, dtype=float), q))


def median(values) -> float:
    return float(np.median(np.asarray(values, dtype=float)))


def spread(values) -> float:
    """Quartile distance over the median, as ``statistics.quantiles`` gives
    the quartiles."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
