"""Admission and lanes: 95th percentile of the wait from a request's due
time to the start of the executor ``run`` that served it."""
import math

from bench.lib.stats import quantile


def read(run):
    q = quantile([r.start - r.due for r in run.reqs
                  if not math.isnan(r.start)], 0.95)
    return None if q is None else q * 1e3
