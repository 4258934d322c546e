"""The program's own spans (``repro.tracing``) reduced to per-layer numbers,
and the trace's idle gaps named by them.

A traced window hands over ``tracing.drain()``: records whose ``start_ns``
and ``end_ns`` are on the wall clock (``time.time_ns``) that the profiler
stamps host events with. A host event's ``start_ns`` in ``ProfileData``
counts from the profile's ``profile_start_time``, so subtracting that
moves a record onto the trace's clock (``trace_start_ns``, ``on_trace``).
Each span is also an annotation in the trace's host plane
(``annotations``); the records add what the plane cannot hold: the
request, the parent, thread CPU time, and ``lane.queue``, which starts on
one thread and ends on another.

Everything here reads plain records, so it is checked on hand-built ones
and on a trace recorded on the CPU (``bench/tests``). A run of a program
without these spans hands over no records, and every reader returns None.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

from bench.lib import trace
from bench.lib.stats import quantile

STORE = ("store.read", "store.callback", "store.host_fetch", "store.cold",
         "store.gather", "store.dedup", "store.lookup_hops")
# the program span a gap is named after, most specific first
GAP_ORDER = STORE + ("executor.sample", "executor.collect", "executor.infer",
                     "executor.sync", "executor.run", "lane.queue",
                     "engine.admit", "router.route", "engine.submit")


def trace_start_ns(log_dir: str) -> int:
    """Wall-clock ns at which the profile of ``log_dir`` started: the zero
    of every ``start_ns`` that ``ProfileData`` gives."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(_xplane(log_dir)).planes:
        stats = dict(plane.stats or ())
        if "profile_start_time" in stats:
            return int(stats["profile_start_time"])
    raise ValueError(f"no profile_start_time in the trace under {log_dir}")


def annotations(log_dir: str, names=GAP_ORDER) -> list[list]:
    """``[name, start_ns, dur_ns]`` of every host-plane event named like a
    program span, on the trace's clock."""
    from jax.profiler import ProfileData

    want = set(names)
    out = []
    for plane in ProfileData.from_file(_xplane(log_dir)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events if e.name in want]
    return out


def _xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    return paths[-1]


def on_trace(records, start_ns: int) -> dict[str, list[tuple[int, int]]]:
    """Intervals of each span name on the trace's clock."""
    out: dict[str, list] = defaultdict(list)
    for r in records:
        out[r.name].append((r.start_ns - start_ns, r.end_ns - start_ns))
    return dict(out)


def idle_by_span(tr: dict, spans: dict) -> list[list]:
    """Idle seconds of the traced window by the program span open at each
    gap's midpoint (first of ``GAP_ORDER``), else ``no_request``; ``tr`` is
    ``trace.load``'s form, ``spans`` ``on_trace``'s."""
    lo, hi = tr["window"]
    gaps = trace.gaps(trace.union(tr["ops"], lo, hi), lo, hi)
    if not gaps:
        return []
    g = np.asarray(gaps, np.int64)
    mid, dur = (g[:, 0] + g[:, 1]) // 2, g[:, 1] - g[:, 0]
    left = np.ones(len(g), bool)
    out = []
    for name in GAP_ORDER:
        hit = left & trace.open_at(spans.get(name, ()), mid)
        if hit.any():
            out.append([name, float(dur[hit].sum()) * 1e-9])
            left &= ~hit
    if left.any():
        out.append(["no_request", float(dur[left].sum()) * 1e-9])
    return sorted(out, key=lambda kv: -kv[1])


def open_across(records, lo_ns: int, hi_ns: int) -> list[str]:
    """Names of the spans open over the whole of [lo_ns, hi_ns] (wall
    clock): what the program was inside of through a host stall."""
    return sorted({r.name for r in records
                   if r.start_ns <= lo_ns and r.end_ns >= hi_ns})


def _within(records, root: str) -> dict[int, list]:
    """For each ``root`` span: the records below it (any depth)."""
    by_id = {r.span_id: r for r in records}
    out: dict[int, list] = {r.span_id: [] for r in records if r.name == root}
    for r in records:
        p = r.parent
        while p is not None and p in by_id:
            if p in out:
                out[p].append(r)
                break
            p = by_id[p].parent
    return out


def lane_wait_p95_ms(records) -> float | None:
    """95th percentile of ``lane.queue``: from the executor's ``submit``
    to its lane starting ``run``."""
    q = quantile([r.end_ns - r.start_ns for r in records
                  if r.name == "lane.queue"], 0.95)
    return None if q is None else q * 1e-6


def collect_read_wait_p50_ms(records) -> float | None:
    """Median over ``store.lookup_hops`` spans of the time spent in the
    ``store.read`` spans below each: blocked on the device."""
    waits = [sum(c.end_ns - c.start_ns for c in kids
                 if c.name == "store.read")
             for kids in _within(records, "store.lookup_hops").values()]
    q = quantile(waits, 0.5)
    return None if q is None else q * 1e-6


def collect_cpu_ms_p50(records) -> float | None:
    """Median thread CPU time of one ``store.lookup_hops`` span, its
    children on the same thread included."""
    q = quantile([r.cpu_ns for r in records
                  if r.name == "store.lookup_hops"], 0.5)
    return None if q is None else q * 1e-6


def self_ms(records) -> dict[str, float]:
    """Mean self time (ms) of each span name: its duration less what its
    children on the same thread cover."""
    child_ns: dict[int, int] = defaultdict(int)
    by_id = {r.span_id: r for r in records}
    for r in records:
        p = by_id.get(r.parent)
        if p is not None and p.thread == r.thread and r.name != "lane.queue":
            child_ns[p.span_id] += r.end_ns - r.start_ns
    tot: dict[str, list] = defaultdict(list)
    for r in records:
        tot[r.name].append(r.end_ns - r.start_ns - child_ns[r.span_id])
    return {k: float(np.mean(v)) * 1e-6 for k, v in tot.items()}


def per_request(count: int | None, completed: int) -> float | None:
    """A window's count over its completed requests."""
    if count is None or not completed:
        return None
    return count / completed
