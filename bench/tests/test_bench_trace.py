"""The reduction from a trace to metrics gives fixed numbers: on a
hand-built trace, and on a small trace recorded on a v5e."""
from __future__ import annotations

import json
import os

import pytest

from benchtest_util import FIXTURES
from bench.lib import trace

MS = 1_000_000


def test_union_gaps_and_names_on_a_hand_built_trace():
    tr = {"window": [0, 100 * MS],
          "ops": [["fusion.1", 10 * MS, 10 * MS],
                  ["fusion.2", 15 * MS, 10 * MS],     # overlaps fusion.1
                  ["copy.3", 50 * MS, 5 * MS],
                  ["copy.3", 98 * MS, 5 * MS],        # runs past the close
                  ["early", -5 * MS, 2 * MS]],        # before the window
          "modules": [["jit_device_sample(1)", 10 * MS, 15 * MS],
                      ["jit_tiered_gather(2)", 50 * MS, 5 * MS]]}
    spans = {"collect": [(30 * MS, 40 * MS)],
             "run.host": [(25 * MS, 49 * MS)],
             "admission": [(56 * MS, 97 * MS)]}
    r = trace.reduce(tr, spans)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.015 + 0.005 + 0.002)
    assert r["op_count"] == 4
    assert r["module_calls"]["jit_device_sample"] == pytest.approx([0.015])
    assert r["module_calls"]["jit_tiered_gather"] == pytest.approx([0.005])
    assert trace.module_times(r, "device_sample") == pytest.approx([0.015])
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.01, "fusion.2": 0.01, "copy.3": 0.01})
    # gaps: [0,10) none open, [25,50) collect at 37.5, [55,98) admission
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"no_request": 0.010, "collect": 0.025, "admission": 0.043})


def test_reduction_of_a_recorded_v5e_trace():
    path = os.path.join(FIXTURES, "trace_v5e.json")
    with open(path) as f:
        fx = json.load(f)
    r = trace.reduce(fx["trace"], {k: [tuple(s) for s in v]
                                   for k, v in fx["spans"].items()})
    want = fx["reduced"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert r["op_count"] == want["op_count"]
    assert {k: sorted(v) for k, v in r["module_calls"].items()} == {
        k: sorted(v) for k, v in want["module_calls"].items()}
    assert r["device_ops"] == want["device_ops"]
    assert r["idle_gaps"] == want["idle_gaps"]
