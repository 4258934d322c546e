"""Cold path: the store's ``host_fetches`` (synchronous ``io_callback``
round trips) per completed request; silent where no row lives off HBM."""


def read(run):
    pl = run.config["placement"]
    if int(pl["host_rows"]) == 0 or not run.completed:
        return None
    return run.counters["host_fetches"] / len(run.completed)
