"""Feature store: the store's ``host_reads`` (blocking device→host reads
on the lookup path) per completed request; silent where the program keeps
no such counter."""
from bench.lib.program import per_request


def read(run):
    return per_request(run.counters.get("host_reads"), len(run.completed))
