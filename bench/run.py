#!/usr/bin/env python3
"""One run of one benchmark cell.

    python bench/run.py --workload products.mixed --seed 1234 \
        --seconds 20 --trace 0

Looks the cell up in ``BENCHMARK.json``, loads its configuration and
traffic mix from ``bench/configs`` and ``bench/traffic``, builds the
program's serving path (set-up, timed as ``setup_s``), serves the mix for
``--seconds`` from the benchmark's own load generator, checks a sample of
the served requests against the plain reference, and prints one JSON
line. With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it records a profiler trace of the window and reports the
per-layer metrics (``bench/metrics/<name>.py``).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. JAX's compile cache and the generated dataset
live in ``bench/.cache`` inside the checkout.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="one run of one benchmark cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup_jax(chips: int, cache: str):
    """Pin the chips and put the compile cache inside the checkout (the
    program's ``use_compile_cache`` takes the directory from the
    environment)."""
    if chips == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def find_chips(jax, chips: int):
    """The chips the cell asks for, or None when JAX finds no TPU or too
    few of them."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return None
    return devs[:chips]


def main(argv=None, *, devices=None, root: str = ROOT) -> int:
    args = parse(argv)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import cells

    cell = cells.workload(args.workload, root)
    cfg = cells.config(cell["config"], root)
    mix = cells.mix(cell, root)
    jax = setup_jax(int(cell["chips"]), os.path.join(
        cells.cache_dir(root), "jax"))
    devs = devices if devices is not None else find_chips(
        jax, int(cell["chips"]))
    if devs is None:
        return 3
    return run_cell(args, cell, cfg, mix, devs, root)


def run_cell(args, cell: dict, cfg: dict, mix: dict, devs, root: str) -> int:
    import jax

    from bench.lib import cells, harness, peaks, trace
    from bench.lib import traffic as tr
    from repro.launch.serve import use_compile_cache

    dev = devs[0]
    log(f"cell {cell['name']}: config {cfg['name']}, traffic {mix['name']}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}; device "
        f"{dev.platform} {dev.device_kind!r}; compile cache "
        f"{use_compile_cache()}")
    peak = peaks.peaks(dev.device_kind) if dev.platform == "tpu" else {}
    rec = harness.Recorder(trace=bool(args.trace))
    jax.monitoring.register_event_duration_secs_listener(
        rec.on_compile_event)

    system = harness.System(cfg, args.seed, rec, root, log=log)
    draw = tr.SeedDraw(system.out_degree, mix["popularity"])
    harness.warm_up(system, mix, args.seconds, draw, rec, log=log)
    system.store.reset_stats()
    routed0 = dict(system.router.routed)
    setup_s = time.monotonic() - T_START
    log(f"setup_s {setup_s}; host peak RSS "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.2f} "
        f"GiB")

    trace_dir = os.path.join(cells.cache_dir(root), "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW), \
            harness.Watch() as watch:
        mono_w0 = time.monotonic()
        reqs, t0 = harness.drive(system, mix, args.seconds, args.seed,
                                 draw, rec)
        mono_w1 = time.monotonic()
    if args.trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    counters = system.store.snapshot_stats()
    routed = {k: v - routed0.get(k, 0)
              for k, v in system.router.routed.items()}
    log(f"window: {len(reqs)} requests attempted, "
        f"{harness.failed(reqs)} failed, routed {routed}, store {counters}")
    log(f"compilations inside the window: {rec.compiles}")
    log(f"host stalls: {watch.summary(t0)}; "
        f"{harness.slowest_stretch(reqs, t0)}")

    caps = harness.host_captures(reqs)
    collect_bytes = (harness.collect_bytes(rec, system.tier, cfg["feat_dim"])
                     if args.trace else None)
    system.close()

    reduced = None
    if args.trace:
        raw = trace.load(trace_dir)
        w0 = raw["window"][0]
        to_ns = lambda t: int(w0 + (t - mono_w0) * 1e9)  # noqa: E731
        spans = {k: [(to_ns(s), to_ns(e)) for s, e in v]
                 for k, v in rec.spans.items()}
        spans["admission"] = [(to_ns(r.due), to_ns(r.start)) for r in reqs
                              if not math.isnan(r.start)]
        reduced = trace.reduce(raw, spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: window {reduced['window_s']} s (host "
            f"{mono_w1 - mono_w0} s), busy {reduced['busy_s']} s, "
            f"programs {sorted(reduced['module_calls'])}")

    run = harness.RunRecord(
        cell=cell["name"], config=cfg, traffic=mix, seconds=args.seconds,
        t0=t0, reqs=reqs, spans=dict(rec.spans), counters=counters,
        routed=routed, collect_bytes=collect_bytes, trace=reduced,
        peaks=peak, flops_per_seed=system.model_mod.flops_per_seed(cfg),
        compiles=rec.compiles)

    chk = cfg["check"]
    numbers, per_exec = harness.check(
        caps, system.model_mod, system.params,
        (system.indptr, system.indices, system.feats), system.fanouts, chk,
        chk["precision"])
    n_failed = harness.failed(reqs)
    checks = {
        "never_completed": [n_failed, "<=", 0],
        "requests_checked": [len(caps), ">=", 1],
        "sample_bad_slots": [numbers["sample_bad_slots"], "<=", 0],
        "row_mismatches": [numbers["row_mismatches"], "<=", 0],
        "output_gap": [numbers["output_gap"], "<=", chk["output_gap"]],
    }
    ok = all((v <= lim) if op == "<=" else (v >= lim)
             for v, op, lim in checks.values())
    log(f"checked {len(caps)} requests by executor {per_exec}")

    kind = "per_layer" if args.trace else "end_to_end"
    wanted = cells.metrics_for(cell["name"], kind, root)
    e2e = harness.end_to_end(run)
    e2e["setup_s"] = setup_s
    metrics = {}
    for m in wanted:
        value = (e2e[m["name"]] if kind == "end_to_end"
                 else cells.reader(m["name"], root)(run))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": ok, "attempted": len(reqs), "failed": n_failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, (v, op, lim) in checks.items():
        print(f"check {name}: {v} {op} {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
