"""Load generator: 95th percentile of how late each send ran against its
due time (open loop only; a closed loop sends when its reply came)."""
from bench.lib.stats import quantile


def read(run):
    if run.traffic["loop"] != "open":
        return None
    q = quantile([r.sent - r.due for r in run.reqs], 0.95)
    return None if q is None else q * 1e3
