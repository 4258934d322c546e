"""Compilations inside the window: the benchmark's listener counts each
compile once, a read from the persistent compile cache included, and
only while the window is open; ``compiles_per_request`` divides them by
the requests attempted and is silent on a run that attempted none."""
from __future__ import annotations

import numpy as np

import benchtest_util  # noqa: F401  (puts the checkout on the path)
from bench.lib import cells
from bench.lib.harness import Recorder, Req, RunRecord

COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _run(n_reqs: int, compiles: int) -> RunRecord:
    reqs = []
    for i in range(n_reqs):
        r = Req(i, np.arange(8), 0.1 * i)
        r.sent, r.start, r.end, r.done = 0.1 * i, 0.1 * i, 0.1 * i + .05, \
            0.1 * i + .06
        r.executor = "device"
        reqs.append(r)
    return RunRecord(
        cell="c", config={}, traffic={"loop": "open"}, seconds=1.0, t0=0.0,
        reqs=reqs, spans={}, counters={}, routed={"device": n_reqs},
        collect_bytes=None, trace=None, peaks={}, flops_per_seed=1,
        compiles=compiles)


def test_compiles_per_request_reader():
    read = cells.reader("compiles_per_request")
    assert read(_run(8, 2)) == 0.25
    assert read(_run(8, 0)) == 0.0
    assert read(_run(0, 3)) is None


def test_the_listener_counts_a_compile_once_and_only_in_the_window():
    rec = Recorder(trace=False)
    rec.on_compile_event(COMPILE, 0.5)            # set-up: not counted
    rec.active = True
    rec.on_compile_event(COMPILE, 0.5)            # a compile
    rec.on_compile_event(CACHE_HIT)               # a cache read sends both
    rec.on_compile_event(COMPILE, 0.01)
    rec.on_compile_event("/jax/compilation_cache/cache_retrieval_time_sec",
                         0.01)
    rec.active = False
    rec.on_compile_event(COMPILE, 0.5)
    assert rec.compiles == 2
