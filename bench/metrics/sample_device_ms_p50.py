"""Sampling: median device time of one call of the jitted
``device_sample`` program, from the trace."""
from bench.lib.stats import quantile
from bench.lib.trace import module_times


def read(run):
    if run.trace is None:
        return None
    q = quantile(module_times(run.trace, "device_sample"), 0.5)
    return None if q is None else q * 1e3
