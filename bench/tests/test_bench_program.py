"""The readers of the program's own spans and counters (``bench/lib/
program.py``, ``host_reads_per_request``): on hand-built records with
hand-computed answers, silent on a program without them, and on a trace
recorded on the CPU, where the gaps come out named the same from the
records as from the annotations in the trace's host plane."""
from __future__ import annotations

import numpy as np
import pytest

import benchtest_util  # noqa: F401  (puts the checkout on the path)
from bench.lib import cells, program
from bench.lib.harness import Req, RunRecord

MS = 1_000_000


def _rec(name, span_id, parent, start_ms, end_ms, cpu_ms=0.0, thread=1):
    from repro.tracing import Record
    return Record(name, int(start_ms * MS), int(end_ms * MS),
                  int(cpu_ms * MS), thread, span_id, parent, 0, {})


# one request: submitted at 0, queued 0.1-2 ms, one lookup with two reads
# and a host fetch whose callback ran on another thread
RECORDS = [
    _rec("engine.submit", 1, None, 0, 0.2),
    _rec("lane.queue", 2, 1, 0.1, 2.0, thread=2),
    _rec("executor.run", 3, 1, 2.0, 20.0, thread=2),
    _rec("executor.collect", 4, 3, 3.0, 15.0, thread=2),
    _rec("store.lookup_hops", 5, 4, 3.0, 15.0, cpu_ms=4.0, thread=2),
    _rec("store.dedup", 6, 5, 3.0, 4.0, thread=2),
    _rec("store.cold", 7, 5, 6.0, 14.0, thread=2),
    _rec("store.read", 8, 7, 6.0, 9.0, thread=2),
    _rec("store.read", 9, 7, 9.0, 10.0, thread=2),
    _rec("store.host_fetch", 10, 7, 10.0, 11.0, thread=2),
    _rec("store.callback", 11, 10, 10.5, 12.0, thread=3),
]


def test_span_reductions_on_hand_built_records():
    assert program.lane_wait_p95_ms(RECORDS) == pytest.approx(1.9)
    assert program.collect_read_wait_p50_ms(RECORDS) == pytest.approx(4.0)
    assert program.collect_cpu_ms_p50(RECORDS) == pytest.approx(4.0)
    self_ms = program.self_ms(RECORDS)
    # 12 ms less dedup 1 and cold 8; cold 8 less reads 4 and fetch 1; the
    # callback ran on its own thread, so it is no part of the fetch's 1 ms
    assert self_ms["store.lookup_hops"] == pytest.approx(3.0)
    assert self_ms["store.cold"] == pytest.approx(3.0)
    assert self_ms["store.host_fetch"] == pytest.approx(1.0)
    # the lane's queue is no child of the submit's own thread
    assert self_ms["engine.submit"] == pytest.approx(0.2)
    assert program.open_across(RECORDS, 6 * MS, 9 * MS) == [
        "executor.collect", "executor.run", "store.cold",
        "store.lookup_hops", "store.read"]
    assert program.per_request(3, 2) == 1.5
    assert program.per_request(None, 2) is None
    assert program.per_request(3, 0) is None


def test_span_reductions_are_silent_without_records():
    assert program.lane_wait_p95_ms([]) is None
    assert program.collect_read_wait_p50_ms([]) is None
    assert program.collect_cpu_ms_p50([]) is None
    assert program.self_ms([]) == {}


def test_idle_gaps_named_by_the_innermost_program_span():
    spans = program.on_trace(RECORDS, 0)
    tr = {"window": [0, 30 * MS],
          "ops": [["a", 1 * MS, 1 * MS],        # idle 0-1: submit/queue
                  ["b", 4 * MS, 2 * MS],        # idle 2-4: mid 3, dedup
                  ["c", 7 * MS, 5 * MS],        # idle 6-7: the read
                  ["d", 12 * MS, 8 * MS]]}      # idle 20-30: none open
    got = dict(program.idle_by_span(tr, spans))
    assert got == pytest.approx({"lane.queue": 0.001, "store.dedup": 0.002,
                                 "store.read": 0.001, "no_request": 0.010})


def _run(counters, done=(1.0, 2.0)):
    reqs = []
    for i, d in enumerate(done):
        r = Req(i, np.arange(4), 0.0)
        r.done = d
        reqs.append(r)
    return RunRecord(cell="c", config={}, traffic={}, seconds=1.0, t0=0.0,
                     reqs=reqs, spans={}, counters=counters, routed={},
                     collect_bytes=None, trace=None, peaks={},
                     flops_per_seed=1)


def test_host_reads_per_request_reader():
    read = cells.reader("host_reads_per_request")
    assert read(_run({"host_reads": 5, "host_fetches": 1})) == 2.5
    # a program without the counter: silent, not an error
    assert read(_run({"host_fetches": 1})) is None
    assert read(_run({"host_reads": 5}, done=())) is None


def test_recorded_trace_names_gaps_from_the_host_plane(tmp_path):
    """A CPU profile around traced lookups: each program span is an
    annotation in the host plane, the records moved onto the trace's clock
    land within 50 us of them, and naming gaps from either gives the same
    names and seconds."""
    import jax
    import jax.numpy as jnp

    from repro import tracing
    from repro.core import TieredFeatureStore, TopologySpec, quiver_placement

    n, d = 64, 8
    feats = np.arange(n * d, dtype=np.float32).reshape(n, d)
    topo = TopologySpec(num_pods=1, devices_per_pod=1, rows_per_device=32,
                        rows_host=32, hot_replicate_fraction=0.5)
    store = TieredFeatureStore.build(
        feats, quiver_placement(np.linspace(1, 0, n), topo))
    # seeds in HBM, their neighbours in host RAM: one host fetch a lookup
    hops = [jnp.arange(8, dtype=jnp.int32),
            jnp.arange(48, 64, dtype=jnp.int32)]
    store.lookup_hops(hops)
    tracing.drain()
    jax.profiler.start_trace(str(tmp_path))
    tracing.enable()
    try:
        for i in range(10):
            with tracing.span("engine.submit", req=i):
                store.lookup_hops(hops)
    finally:
        tracing.disable()
        jax.profiler.stop_trace()
    records = tracing.drain()
    start = program.trace_start_ns(str(tmp_path))
    ann = program.annotations(str(tmp_path))
    assert {a[0] for a in ann} == {r.name for r in records} >= {
        "engine.submit", "store.lookup_hops", "store.dedup", "store.gather",
        "store.cold", "store.read", "store.host_fetch"}
    from_ann: dict[str, list] = {}
    for name, s, dur in ann:
        from_ann.setdefault(name, []).append((s, s + dur))
    from_rec = program.on_trace(records, start)
    # same clock: each record is stamped a few us inside its annotation
    # (a thread switched out between the two can add to the odd one)
    off = np.concatenate([
        np.asarray(sorted(iv)) - np.asarray(sorted(from_ann[name]))
        for name, iv in from_rec.items()])
    assert len(off) == len(ann)
    assert np.median(np.abs(off[:, 0])) < 50_000
    assert np.median(np.abs(off[:, 1])) < 50_000
    # the device idle for 0.2 ms in the middle of each host fetch and busy
    # the rest of the time: the gaps are named after the fetch (or its
    # callback, if that runs then), alike from the host plane and from
    # the records moved onto the trace's clock
    mids = sorted((s + e) // 2 for s, e in from_ann["store.host_fetch"])
    lo, hi = mids[0] - 10 * MS, mids[-1] + 10 * MS
    edges = [lo] + [t for m in mids for t in (m - MS // 10, m + MS // 10)]
    edges.append(hi)
    tr = {"window": [lo, hi],
          "ops": [["busy", edges[i], edges[i + 1] - edges[i]]
                  for i in range(0, len(edges), 2)]}
    by_ann = dict(program.idle_by_span(tr, from_ann))
    assert set(by_ann) <= {"store.host_fetch", "store.callback"}
    assert sum(by_ann.values()) == pytest.approx(len(mids) * 2e-4)
    assert dict(program.idle_by_span(tr, from_rec)) == pytest.approx(by_ann)
