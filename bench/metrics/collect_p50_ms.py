"""Feature store: median host span of ``store.lookup_hops``, from its call
to its return: dispatch and the syncs the store makes itself, no sync
added (traced runs wrap it)."""
from bench.lib.stats import quantile


def read(run):
    q = quantile([e - s for s, e in run.spans.get("collect", ())], 0.5)
    return None if q is None else q * 1e3
