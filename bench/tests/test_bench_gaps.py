"""Idle gaps are named by one sort and a count per span name
(``trace.name_gaps``), exactly as the scan over every span that it
replaced named them, on spans that overlap, nest, touch and are empty."""
from __future__ import annotations

import numpy as np

import benchtest_util  # noqa: F401  (puts the checkout on the path)
from bench.lib import trace


def scan(mid: int, spans: dict) -> str:
    """The former ``trace.name_gap``: every span list scanned per gap."""
    for name in trace.GAP_ORDER:
        for s, e in spans.get(name, ()):
            if s <= mid < e:
                return name
    return "no_request"


def overlapping_spans(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    spans = {}
    for name in trace.GAP_ORDER[:-1]:          # one name never opens
        start = rng.integers(0, 10_000, 60)
        dur = rng.integers(0, 400, 60)         # some empty: [s, s)
        dur[:3] = [5_000, 0, 1]                # a long one that others nest in
        spans[name] = list(zip(start.tolist(), (start + dur).tolist()))
    return spans


def test_names_match_the_scan_on_overlapping_spans():
    for seed in range(5):
        spans = overlapping_spans(seed)
        edges = sorted({t for iv in spans.values() for se in iv for t in se})
        mids = np.array(edges + [t - 1 for t in edges] + [-5, 20_000]
                        + list(range(0, 10_500, 7)), np.int64)
        want = [scan(int(m), spans) for m in mids]
        assert trace.name_gaps(mids, spans) == want
        assert set(want) >= {"collect", "no_request"}


def test_reduce_names_every_gap_as_the_scan_does():
    spans = overlapping_spans(7)
    ops = [["op", int(s), int(d)] for s, d in zip(
        range(0, 10_000, 150), np.random.default_rng(1).integers(1, 120, 67))]
    tr = {"window": [0, 10_000], "ops": ops, "modules": []}
    busy = trace.union(ops, 0, 10_000)
    idle: dict[str, int] = {}
    for s, e in trace.gaps(busy, 0, 10_000):
        name = scan((s + e) // 2, spans)
        idle[name] = idle.get(name, 0) + e - s
    got = dict(trace.reduce(tr, spans, top=len(trace.GAP_ORDER) + 1)[
        "idle_gaps"])
    assert got == {k: v * 1e-9 for k, v in idle.items()}


def test_no_spans_name_every_gap_no_request():
    assert trace.name_gaps([0, 5, 9], {}) == ["no_request"] * 3
    assert trace.name_gaps([], {"collect": [(0, 3)]}) == []
