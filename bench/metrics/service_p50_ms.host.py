"""Executor: median span of the ``host`` executor's ``run`` (sampling,
collection and the model, ending in ``block_until_ready``) over the
window's requests it served."""
from bench.lib.stats import quantile


def read(run):
    q = quantile([r.end - r.start for r in run.reqs
                  if r.executor == "host"], 0.5)
    return None if q is None else q * 1e3
