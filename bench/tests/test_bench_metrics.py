"""Each per-layer metric reader and the FLOP and byte counts, on a
hand-built run record with hand-computed answers."""
from __future__ import annotations

import math

import numpy as np
import pytest

import benchtest_util  # noqa: F401  (puts the checkout on the path)
from bench.lib import cells, counts
from bench.lib.harness import Req, RunRecord, end_to_end

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _req(i, n, due, sent, start, end, done, ex):
    r = Req(i, np.arange(n), due)
    r.sent, r.start, r.end, r.done, r.executor = sent, start, end, done, ex
    return r


def _run(loop="open", host_rows=10, trace=None, collect_bytes=None):
    reqs = [
        _req(0, 4, 0.0, 0.001, 0.002, 0.012, 0.013, "host"),
        _req(1, 100, 0.1, 0.103, 0.110, 0.150, 0.151, "device"),
        _req(2, 10, 0.2, 0.200, 0.230, 0.250, 0.251, "host"),
        _req(3, 50, 0.3, 0.304, 0.320, 0.400, 0.401, "device"),
    ]
    return RunRecord(
        cell="c", config={"placement": {"host_rows": host_rows}},
        traffic={"loop": loop}, seconds=1.0, t0=0.0, reqs=reqs,
        spans={"collect": [(0.0, 0.004), (0.1, 0.102), (0.2, 0.209)]},
        counters={"host_fetches": 6}, routed={"host": 2, "device": 2},
        collect_bytes=collect_bytes, trace=trace, peaks=PEAKS,
        flops_per_seed=884_736)


def read(name, run):
    return cells.reader(name)(run)


def test_sage_flops_per_seed_matches_the_hand_count():
    # 16 nodes run layer 1 (d -> 128), the seed runs layer 2 (128 -> 128)
    assert counts.sage_flops_per_seed(100, (128, 128), (15, 10)) == 884_736
    assert counts.sage_flops_per_seed(128, (128, 128), (15, 10)) == 1_114_112
    assert counts.sage_flops_per_seed(4, (2,), (3,)) == 2 * 2 * 4 * 2


def test_gather_bytes_counts_distinct_hbm_rows_at_logical_width():
    tier = np.array([0, 1, 2, 3, 1])
    ids = np.array([0, 0, 1, 2, 3, 4, -1, -1])
    # distinct valid ids 0..4; HBM tiers (0, 1): ids 0, 1, 4
    assert counts.gather_bytes(ids, tier, 100) == 3 * 2 * 100 * 4


def test_end_to_end_numbers():
    e2e = end_to_end(_run())
    assert e2e["seeds_per_s"] == 164.0
    lat = [0.013, 0.051, 0.051, 0.101]
    assert e2e["latency_p50_ms"] == pytest.approx(np.quantile(lat, .5) * 1e3)
    assert e2e["latency_p95_ms"] == pytest.approx(np.quantile(lat, .95) * 1e3)


def test_a_request_that_never_came_counts_as_missing():
    run = _run()
    run.reqs[3].done = math.nan
    e2e = end_to_end(run)
    assert e2e["seeds_per_s"] == 114.0
    assert e2e["latency_p95_ms"] > 50_000         # waited out the grace


def test_generator_and_admission_readers():
    assert read("gen_lag_p95_ms", _run()) == pytest.approx(
        np.quantile([0.001, 0.003, 0.0, 0.004], 0.95) * 1e3)
    assert read("gen_lag_p95_ms", _run(loop="closed")) is None
    assert read("admission_wait_p95_ms", _run()) == pytest.approx(
        np.quantile([0.002, 0.010, 0.030, 0.020], 0.95) * 1e3)


def test_routing_and_executor_readers():
    assert read("device_route_share", _run()) == 50.0
    assert read("service_p50_ms.device", _run()) == pytest.approx(60.0)
    assert read("service_p50_ms.host", _run()) == pytest.approx(15.0)
    run = _run()
    run.reqs = [r for r in run.reqs if r.executor == "device"]
    assert read("service_p50_ms.host", run) is None


def test_store_readers():
    assert read("collect_p50_ms", _run()) == pytest.approx(4.0)
    assert read("cold_fetches_per_request", _run()) == 1.5
    assert read("cold_fetches_per_request", _run(host_rows=0)) is None


def test_step_mfu():
    busy = 0.010 + 0.040 + 0.020 + 0.080
    want = 100 * 884_736 * 164 / busy / 197e12
    assert read("step_mfu", _run()) == pytest.approx(want)


def test_trace_readers():
    trace = {"busy_s": 0.25, "window_s": 1.0, "op_count": 3,
             "module_calls": {"jit_device_sample": [0.001, 0.003, 0.002],
                              "jit_tiered_gather": [0.002, 0.002]}}
    run = _run(trace=trace, collect_bytes=819_000)
    assert read("device_idle_share", run) == 75.0
    assert read("sample_device_ms_p50", run) == pytest.approx(2.0)
    # least time 819 kB / 819 GB/s = 1 us over 4 ms of kernel time
    assert read("gather_roofline", run) == pytest.approx(0.025)
    assert read("gather_roofline", _run(trace=trace)) is None
    assert read("sample_device_ms_p50", _run()) is None
