"""Find a cell's configuration, traffic mix, served model and metric
readers by name.

Everything that belongs to one configuration, one traffic mix, one served
model or one per-layer metric is a file of its own under ``bench/``:

    bench/configs/<config>.json   sizes, placement, executor settings, and
                                  ``model``: the name of the served model
    bench/traffic/<mix>.json      loop, clients, request sizes, popularity
    bench/cells/<cell>.json       what one cell sets of its mix: the
                                  offered rate, 4/5 of that cell's knee
    bench/models/<model>.py       the served model (below)
    bench/metrics/<metric>.py     ``read(run) -> float | None``

and ``BENCHMARK.json`` at the checkout root names the cells. Adding a cell
adds files and a ``workloads`` entry; no file here changes, and a mix
serves any configuration unedited.

A served model is a config whose ``model`` names a new
``bench/models/<model>.py`` (and metric readers of its own, where it has
kernels of its own). The file gives four functions:

    make_params(seed, cfg)      weights made on the device from ``--seed``
    served(params, cfg)         the ``infer_fn(hop_feats, hop_ids,
                                deep_agg=None)`` handed to the executors,
                                built on the program's own model code
    reference(params, hop_rows, hop_ids, fanouts, *, dtype, precision)
                                plain ``jax.numpy``, importing nothing of
                                the program: what ``correct`` compares with
    flops_per_seed(cfg)         model FLOPs one seed requires (``step_mfu``)
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

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


_MODELS: dict[str, object] = {}


def model(name: str, root: str | None = None):
    """The served model ``bench/models/<name>.py`` of the checkout at
    ``root`` (a model that root does not hold is the harness's own),
    executed once a process, so every caller gets the same module."""
    path = os.path.join(root or ROOT, "bench", "models", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, "models", f"{name}.py")
    if path not in _MODELS:
        spec = importlib.util.spec_from_file_location(
            f"bench_model_{len(_MODELS)}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODELS[path] = mod
    return _MODELS[path]
