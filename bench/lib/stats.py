"""Arithmetic over raw samples (copied from the program's
``benchmarks/common.latency_percentiles``: quantiles of the samples
themselves, never of a histogram)."""
from __future__ import annotations

import statistics

import numpy as np


def quantile(samples, q: float) -> float | None:
    """``q`` quantile of the raw samples; None when there are none."""
    if len(samples) == 0:
        return None
    return float(np.quantile(np.asarray(samples, np.float64), q))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(n=4)``, the bound's yardstick)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
