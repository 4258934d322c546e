"""Device: share of the traced window in which no operation ran on the
chip (1 - busy / window, busy the union of the op intervals); silent
where the trace holds no chip."""


def read(run):
    if run.trace is None or not run.trace["op_count"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
