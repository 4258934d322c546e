"""A throwaway checkout root holding a test-only cell, for the tests."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CELLS = [
    {"name": "tiny.open", "config": "tiny-sage2", "traffic": "tiny_open",
     "chips": 1, "why": "test only"},
    {"name": "tiny.closed", "config": "tiny-sage2",
     "traffic": "tiny_closed", "chips": 1, "why": "test only"},
]


def make_root(tmp: str) -> str:
    """A root whose BENCHMARK.json is the real one plus the tiny cells,
    with the real metric readers and the tiny config and traffic."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] += TINY_CELLS
    names = [c["name"] for c in TINY_CELLS]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + names
    os.makedirs(tmp, exist_ok=True)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for kind in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(tmp, "bench", kind), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(tmp, "bench", "metrics"),
                    dirs_exist_ok=True)
    shutil.copy(os.path.join(FIXTURES, "tiny-sage2.json"),
                os.path.join(tmp, "bench", "configs"))
    for t in ("tiny_open", "tiny_closed"):
        shutil.copy(os.path.join(FIXTURES, f"{t}.json"),
                    os.path.join(tmp, "bench", "traffic"))
    shutil.copytree(os.path.join(FIXTURES, "cells"),
                    os.path.join(tmp, "bench", "cells"), dirs_exist_ok=True)
    return tmp


def run_main(root: str, argv: list[str]) -> int:
    """``bench/run.py``'s main on the CPU devices (the look for a chip
    skipped), restoring the JAX settings it changes for the process."""
    import contextlib
    import importlib

    import jax

    run = importlib.import_module("bench.run")
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = {k: os.environ.get(k) for k in (
        "JAX_COMPILATION_CACHE_DIR", "TPU_VISIBLE_CHIPS",
        "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")}
    try:
        return run.main(argv, devices=jax.devices()[:1], root=root)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        with contextlib.suppress(Exception):
            from jax.experimental.compilation_cache import compilation_cache
            compilation_cache.reset_cache()
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
