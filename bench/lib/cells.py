"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``bench/``:

    bench/configs/<config>.json   sizes, placement, executor settings
    bench/traffic/<mix>.json      loop, clients, request sizes, popularity
    bench/cells/<cell>.json       what one cell sets of its mix: the
                                  offered rate, 4/5 of that cell's knee
    bench/metrics/<metric>.py     ``read(run) -> float | None``

and ``BENCHMARK.json`` at the checkout root names the cells. Adding a cell
adds files and a ``workloads`` entry; no file here changes, and a mix
serves any configuration unedited.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def cache_dir(root: str | None = None) -> str:
    """Git-ignored per-checkout cache: data and the compile cache."""
    return os.path.join(root or ROOT, "bench", ".cache")


def benchmark(root: str | None = None) -> dict:
    with open(os.path.join(root or ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str, root: str | None) -> dict:
    path = os.path.join(root or ROOT, "bench", kind, f"{name}.json")
    with open(path) as f:
        out = json.load(f)
    out.setdefault("name", name)
    return out


def config(name: str, root: str | None = None) -> dict:
    return _json("configs", name, root)


def traffic(name: str, root: str | None = None) -> dict:
    return _json("traffic", name, root)


def mix(cell: dict, root: str | None = None) -> dict:
    """The cell's traffic: its mix file, with the keys of
    ``bench/cells/<cell>.json`` (when there is one) set over it."""
    out = traffic(cell["traffic"], root)
    path = os.path.join(root or ROOT, "bench", "cells",
                        f"{cell['name']}.json")
    if os.path.exists(path):
        with open(path) as f:
            out.update(json.load(f))
    return out


def workload(name: str, root: str | None = None) -> dict:
    """The ``workloads`` entry of cell ``name``; KeyError if absent."""
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(cell: str, kind: str, root: str | None = None) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that cell reports."""
    return [m for m in benchmark(root)[kind]
            if cell in m.get("workloads", [cell])]


def reader(metric: str, root: str | None = None):
    """``read`` of ``bench/metrics/<metric>.py`` (file names may hold dots,
    so it is loaded by path, not imported by module name)."""
    path = os.path.join(root or ROOT, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
