"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the JAX profiler wrote into a small,
plain form (lists of ``[name, start_ns, dur_ns]``):

    ops       device operations (the ``XLA Ops`` line of the chip's plane)
    modules   jitted programs run on the chip (the ``XLA Modules`` line)
    window    [start_ns, end_ns] of the ``bench.window`` annotation

``reduce`` works only on that form, so it is checked on a recorded one
(``bench/tests/fixtures``). Busy time is the union of the op intervals
inside the window; the idle gaps between them are named by the
benchmark's host spans that were open at each gap's midpoint.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
# the host span a gap is named after, most specific first
GAP_ORDER = ("collect", "run.device", "run.host", "generator", "admission")


def load(log_dir: str, device_index: int = 0) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"ops": [], "modules": [], "window": None}
    dev_name = f"/device:TPU:{device_index}"
    for plane in data.planes:
        if plane.name == dev_name:
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key] += [[e.name, int(e.start_ns),
                                  int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        out["window"] = [int(e.start_ns),
                                         int(e.start_ns + e.duration_ns)]
    return out


def op_name(hlo: str) -> str:
    """An op event's name is its HLO instruction; keep the name and the
    result shape (``%tiered_gather.1 = f32[21248,128]``)."""
    return hlo.split("{")[0].split("(")[0].strip()


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for _, s, d in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def open_at(intervals, ts: np.ndarray) -> np.ndarray:
    """For each time in ``ts``: is any interval [s, e) open there. Sorts
    the starts and the ends once and counts, for each time, how many
    intervals have started less how many have ended."""
    iv = np.asarray([(s, e) for s, e in intervals if s < e],
                    np.int64).reshape(-1, 2)
    s, e = np.sort(iv[:, 0]), np.sort(iv[:, 1])
    return (np.searchsorted(s, ts, side="right")
            - np.searchsorted(e, ts, side="right")) > 0


def name_gaps(mids, spans: dict[str, list[tuple[int, int]]]) -> list[str]:
    """The name of each gap midpoint: the first span of ``GAP_ORDER`` open
    there, else ``no_request``."""
    mids = np.asarray(mids, np.int64)
    names = np.full(mids.shape, "no_request", dtype=object)
    left = np.ones(mids.shape, bool)
    for name in GAP_ORDER:
        hit = left & open_at(spans.get(name, ()), mids)
        names[hit] = name
        left &= ~hit
    return names.tolist()


def reduce(tr: dict, spans: dict[str, list[tuple[int, int]]] | None = None,
           top: int = 10) -> dict:
    """Busy and window seconds, per-program device times, the top device
    ops, and idle seconds by the host span open at the time.

    ``spans`` maps a gap name of ``GAP_ORDER`` to intervals on the trace's
    clock (ns)."""
    lo, hi = tr["window"]
    busy = union(tr["ops"], lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    per_op: dict[str, int] = defaultdict(int)
    for name, s, d in tr["ops"]:
        if lo <= s < hi:
            per_op[op_name(name)] += d
    calls: dict[str, list[float]] = defaultdict(list)
    for name, s, d in tr["modules"]:
        if lo <= s < hi:
            calls[name.split("(")[0]].append(d * 1e-9)
    idle: dict[str, int] = defaultdict(int)
    idle_iv = gaps(busy, lo, hi)
    for (s, e), name in zip(idle_iv, name_gaps(
            [(s + e) // 2 for s, e in idle_iv], spans or {})):
        idle[name] += e - s
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return {"busy_s": busy_ns * 1e-9, "window_s": (hi - lo) * 1e-9,
            "op_count": sum(lo <= s < hi for _, s, _ in tr["ops"]),
            "module_calls": dict(calls),
            "device_ops": [[k, v * 1e-9] for k, v in rank(per_op)],
            "idle_gaps": [[k, v * 1e-9] for k, v in rank(idle)]}


def module_times(reduced: dict, needle: str) -> list[float]:
    """Per-call device seconds of every program whose name holds
    ``needle`` (e.g. ``jit_device_sample``)."""
    return [t for name, ts in reduced["module_calls"].items()
            if needle in name for t in ts]
