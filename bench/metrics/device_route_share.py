"""Routing: share of the window's requests that the router sent to the
``device`` executor (``CostModelRouter.routed``), in percent."""


def read(run):
    total = sum(run.routed.values())
    if total == 0:
        return None
    return 100.0 * run.routed.get("device", 0) / total
