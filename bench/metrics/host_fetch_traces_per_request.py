"""Cold path: traces of the store's jitted host-fetch program
(``host_fetch_traces``; each is followed by a compile) per completed
request; silent where the program keeps no such counter."""
from bench.lib.program import per_request


def read(run):
    return per_request(run.counters.get("host_fetch_traces"),
                       len(run.completed))
