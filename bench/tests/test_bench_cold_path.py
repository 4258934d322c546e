"""The reader of the cold path's fetch-program counter
(``host_fetch_traces_per_request``): on a hand-built run record with a
hand-computed answer, and silent on a program without the counter or a
run in which nothing completed."""
from __future__ import annotations

import numpy as np

import benchtest_util  # noqa: F401  (puts the checkout on the path)
from bench.lib import cells
from bench.lib.harness import Req, RunRecord


def _run(counters):
    reqs = []
    for i in range(4):
        r = Req(i, np.arange(8), 0.1 * i)
        r.sent, r.start, r.end, r.done = 0.1 * i, 0.1 * i, 0.1 * i + .05, \
            0.1 * i + .06
        r.executor = "device"
        reqs.append(r)
    return RunRecord(
        cell="c", config={"placement": {"host_rows": 10}},
        traffic={"loop": "open"}, seconds=1.0, t0=0.0, reqs=reqs,
        spans={}, counters=counters, routed={"device": 4},
        collect_bytes=None, trace=None, peaks={}, flops_per_seed=1)


def read(run):
    return cells.reader("host_fetch_traces_per_request")(run)


def test_host_fetch_traces_per_request_reader():
    assert read(_run({"host_fetches": 3, "host_fetch_traces": 2})) == 0.5
    assert read(_run({"host_fetches": 3, "host_fetch_traces": 0})) == 0.0
    # the parent keeps no such counter: silent, not an error
    assert read(_run({"host_fetches": 3})) is None
    run = _run({"host_fetch_traces": 2})
    run.reqs = []
    assert read(run) is None
