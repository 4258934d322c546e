#!/usr/bin/env python3
"""Smoke run of the Quiver serving path on a TPU at the ogbn-products shape.

    python chip_smoke.py           # one chip: fused, then fuse_aggregate
    python chip_smoke.py --mesh    # every chip of the host: sharded path

Drives the launcher's own path in this one process
(``repro.launch.serve``: ``build_stack`` → ``build_executors`` →
``calibrate_executors`` → ``CostModelRouter`` → ``ServingEngine``) over a
seeded power-law graph with the ogbn-products shape — 2,449,029 nodes,
61,859,140 edges (average degree 25.26), d=100 — sampled at fanouts
(15, 10) and served by the ``sage-base`` GraphSAGE (hidden 128, 128;
random weights from seed 0). The launcher's default placement spreads the
rows over the HOT, WARM, HOST and DISK tiers, so the host-callback cold
path runs too.

One-chip phases, each serving ``REQUESTS`` requests of ``MAX_BATCH`` seeds:

  fused           ``lookup_hops`` → the ``tiered_gather`` Pallas kernel
  fuse_aggregate  ``lookup_aggregate`` → the ``gather_aggregate`` kernel

Each phase checks that every request completed, none was shed, batches
were routed to the ``device`` executor, the store counted its device
gathers and the compiled gather program holds a Mosaic kernel
(``tpu_custom_call``). On one fixed sampled batch it checks the hop
feature rows are bit-identical to ``feats[ids]`` taken in numpy, the fused
aggregate is within ``AGG_TOL`` of a numpy float64 sum, and the model
output is within ``OUT_TOL`` of a plain ``jax.numpy`` float32 GraphSAGE
(highest matmul precision) on the same subgraph.

``--mesh`` runs only the sharded path: ``build_sharded_store`` over all
devices, ``ShardedFeatureStore.lookup_hops`` through the ``alltoall``
exchange checked bit-identical to the one-chip ``TieredFeatureStore`` on
the same hops (HOST ids included), and a few requests through the
``ShardedExecutor``.

Earlier lines report the device, timings (a smoke run, not a benchmark)
and per-phase counts; the last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``. Exits non-zero, without that line, when
JAX finds no TPU or any check fails. The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` at the root of
the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __name__ == "__main__" and "--mesh" not in sys.argv[1:]:
    # one process, one chip — even on a host with several
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

NODES = 2_449_029
EDGES = 61_859_140
D_FEAT = 100
FANOUTS = (15, 10)
HIDDEN = (128, 128)          # the sage-base preset build_stack serves
MAX_BATCH = 128
REQUESTS = 32
SEED = 0
# fused aggregate vs the float64 numpy sum: sequential fp32 summation of
# ≤10 unit-normal rows errs by ≲ 10 · 6e-8 · Σ|x| ≈ 2e-5
AGG_TOL = dict(rtol=1e-5, atol=1e-4)
# served model (TPU default matmul precision: one bf16 pass) vs the
# highest-precision float32 reference; the layer-normed outputs are O(1),
# and a bf16-operand emulation of the same model deviates by ≤ 0.015
OUT_TOL = dict(rtol=1e-2, atol=5e-2)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def sage_reference(params, hop_rows, hop_ids, fanouts):
    """Plain jax.numpy float32 layered GraphSAGE (mean aggregator,
    layer norm, ReLU between layers) at the highest matmul precision."""
    hp = jax.lax.Precision.HIGHEST
    h = [jnp.asarray(r, jnp.float32) for r in hop_rows]
    masks = [jnp.asarray((np.asarray(i) >= 0).astype(np.float32))
             for i in hop_ids]
    n_layers = len(params["layers"])
    for layer, p in enumerate(params["layers"]):
        nxt = []
        for lvl in range(n_layers - layer):
            fan = fanouts[lvl]
            child = h[lvl + 1].reshape(h[lvl].shape[0], fan, -1)
            m = masks[lvl + 1].reshape(h[lvl].shape[0], fan, 1)
            agg = (child * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
            z = (jnp.dot(h[lvl], p["self"]["w"], precision=hp)
                 + p["self"]["b"]
                 + jnp.dot(agg, p["neigh"]["w"], precision=hp)
                 + p["neigh"]["b"])
            mu = z.mean(-1, keepdims=True)
            var = ((z - mu) ** 2).mean(-1, keepdims=True)
            z = (z - mu) / jnp.sqrt(var + 1e-5) * p["ln"]["g"] + p["ln"]["b"]
            nxt.append(z if layer == n_layers - 1 else jnp.maximum(z, 0.0))
        h = nxt
    return h[0]


def rows_of(feats: np.ndarray, ids) -> np.ndarray:
    ids = np.asarray(ids)
    return np.where((ids >= 0)[:, None], feats[np.maximum(ids, 0)], 0.0
                    ).astype(feats.dtype)


def fixed_hops(graph, graph_dev):
    """One fixed degree-weighted seed batch, sampled on the device."""
    from repro.graph.sampler import device_sample
    rng = np.random.default_rng(SEED + 7)
    p = graph.out_degree.astype(np.float64) + 1e-6
    seeds = rng.choice(graph.num_nodes, size=MAX_BATCH, p=p / p.sum())
    return device_sample(jax.random.key(SEED + 7), *graph_dev,
                         jnp.asarray(seeds, jnp.int32), FANOUTS)


def has_kernel(jitted, *args) -> bool:
    """Whether the TPU program ``jitted`` compiles to holds a Mosaic
    kernel (interpret mode or the jnp oracle would not)."""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def model_params():
    from repro.models.gnn_basic import sage_init
    return sage_init(jax.random.key(SEED), [D_FEAT, *HIDDEN])


def check_output(out, ref_rows, hops, what: str) -> float:
    out = np.asarray(out)
    ref = np.asarray(sage_reference(model_params(), ref_rows,
                                    [np.asarray(h) for h in hops], FANOUTS))
    check(out.shape == (MAX_BATCH, HIDDEN[-1]), f"{what}: shape {out.shape}")
    check(np.isfinite(out).all(), f"{what}: non-finite model output")
    err = float(np.abs(out - ref).max())
    check(np.allclose(out, ref, **OUT_TOL),
          f"{what}: model output off the float32 reference by {err}")
    return err


def serve_phase(stack, graph_dev, *, fuse_aggregate: bool) -> dict:
    from repro.kernels.gather_aggregate.ops import gather_aggregate
    from repro.kernels.tiered_gather.ops import tiered_gather
    from repro.launch.serve import build_executors
    from repro.serving import (CostModelRouter, ServingEngine,
                               calibrate_executors)

    graph, feats, psgs, fap, store, gen, infer_fn = stack
    name = "fuse_aggregate" if fuse_aggregate else "fused"
    t0 = time.perf_counter()
    executors = build_executors(graph, store, FANOUTS, infer_fn, psgs,
                                num_workers=2, max_batch=MAX_BATCH,
                                sharded=False, fused=True,
                                fuse_aggregate=fuse_aggregate)
    order = np.argsort(psgs)
    batches = [order[int(q * graph.num_nodes):][:MAX_BATCH].astype(np.int64)
               for q in np.linspace(0.05, 0.95, 8)]
    curves = calibrate_executors(executors, batches, psgs, repeats=2)
    router = CostModelRouter.from_curves(psgs, curves, "latency_preferred",
                                         executors=executors)
    engine = ServingEngine(executors, router, max_inflight=64,
                           admission="wait")
    reqs = list(gen.stream(REQUESTS, seeds_per_request=MAX_BATCH))
    engine.warmup([reqs[0]])
    compile_s = time.perf_counter() - t0

    store.reset_stats()
    t0 = time.perf_counter()
    summary = engine.run([[r] for r in reqs]).summary()
    serve_s = time.perf_counter() - t0
    engine.close()
    stats = summary["store"].get("TieredFeatureStore", {})
    log(f"{name}: routed {summary['routed']}, store {stats}")
    check(summary["requests"] == REQUESTS,
          f"{name}: {summary['requests']} of {REQUESTS} requests completed")
    check(summary["shed"] == 0, f"{name}: {summary['shed']} shed")
    check(summary["routed"].get("device", 0) > 0,
          f"{name}: no batch routed to the device executor")
    check(stats.get("collect_mode") == name,
          f"{name}: collect_mode {stats.get('collect_mode')!r}")
    check(stats.get("device_gathers", 0) > 0, f"{name}: no device gathers")
    calls = "fused_aggregates" if fuse_aggregate else "fused_calls"
    check(stats.get(calls, 0) > 0, f"{name}: no {calls}")

    # one fixed batch, checked against numpy and the float32 reference
    hops = fixed_hops(graph, graph_dev)
    ref_rows = [rows_of(feats, h) for h in hops]
    sizes = [int(h.shape[0]) for h in hops]
    total = sum(sizes)
    i32 = jnp.int32
    if fuse_aggregate:
        outer, agg = store.lookup_aggregate(hops)
        for k, (got, want) in enumerate(zip(outer, ref_rows)):
            check(np.array_equal(np.asarray(got), want),
                  f"{name}: hop {k} rows differ from feats[ids]")
        inner = np.asarray(hops[-1]).reshape(sizes[-2], FANOUTS[-1])
        want = rows_of(feats, inner.reshape(-1)).astype(np.float64).reshape(
            sizes[-2], FANOUTS[-1], -1).sum(1)
        agg_err = float(np.abs(np.asarray(agg) - want).max())
        check(np.allclose(np.asarray(agg), want, **AGG_TOL),
              f"{name}: fused aggregate off the numpy sum by {agg_err}")
        out = infer_fn(outer, hops, deep_agg=agg)
        kernel = has_kernel(
            gather_aggregate,
            jax.ShapeDtypeStruct((total + sizes[-2], FANOUTS[-1]), i32),
            jax.ShapeDtypeStruct((total + sizes[-2], FANOUTS[-1]), i32),
            store.hot, store.warm,
            jax.ShapeDtypeStruct((1024, store.hot.shape[1]), store.hot.dtype))
    else:
        got = store.lookup_hops(hops)
        for k, (g, want) in enumerate(zip(got, ref_rows)):
            check(np.array_equal(np.asarray(g), want),
                  f"{name}: hop {k} rows differ from feats[ids]")
        agg_err = None
        out = infer_fn(got, hops)
        kernel = has_kernel(tiered_gather,
                            jax.ShapeDtypeStruct((total,), i32),
                            jax.ShapeDtypeStruct((total,), i32),
                            store.hot, store.warm)
    check(kernel, f"{name}: no tpu_custom_call in the compiled gather")
    out_err = check_output(out, ref_rows, hops, name)
    log(f"{name}: compile+calibrate {compile_s} s, serve "
        f"{REQUESTS} requests {serve_s} s, p50 {summary['p50_ms']} "
        f"ms, p99 {summary['p99_ms']} ms (smoke timings, not a "
        f"benchmark); agg err {agg_err}, output err {out_err}")
    return {"requests": summary["requests"], "shed": summary["shed"],
            "routed": summary["routed"], "store": stats,
            "compile_s": compile_s, "serve_s": serve_s,
            "agg_max_err": agg_err, "out_max_err": out_err}


def mesh_phase(stack, graph_dev) -> dict:
    from repro.core.placement import TIER_DISK, TIER_HOST
    from repro.launch.serve import build_executors
    from repro.serving import ServingEngine, StaticScheduler

    graph, feats, psgs, fap, store, gen, infer_fn = stack
    world = len(jax.devices())
    check(world >= 2, f"--mesh needs several devices, JAX found {world}")
    t0 = time.perf_counter()
    executors = build_executors(graph, store, FANOUTS, infer_fn, psgs,
                                num_workers=1, max_batch=MAX_BATCH,
                                sharded=True, feats=feats, fap=fap)
    sex = executors["sharded"]
    sstore = sex.sstore
    build_s = time.perf_counter() - t0
    shards = sstore.warm.addressable_shards
    devs = {s.device for s in shards}
    log(f"mesh: warm {sstore.warm.shape} over {len(devs)} devices, "
        f"{sstore.rows_per_dev} rows each; sharded store built in "
        f"{build_s} s")
    check(len(devs) == world and all(
        s.data.shape[0] == sstore.rows_per_dev for s in shards),
        f"mesh: warm shards on {len(devs)} of {world} devices")

    hops = fixed_hops(graph, graph_dev)
    ids = np.concatenate([np.asarray(h) for h in hops])
    tiers = sstore.tier_table_host[ids[ids >= 0]]
    n_host = int((tiers == TIER_HOST).sum())
    n_disk = int((tiers == TIER_DISK).sum())
    sstore.reset_stats()
    t0 = time.perf_counter()
    rows_s = sstore.lookup_hops(hops)
    jax.block_until_ready(rows_s)
    lookup_s = time.perf_counter() - t0
    rows_1 = store.lookup_hops(hops)
    sstats = sstore.snapshot_stats()
    log(f"mesh: fixed batch {ids.size} ids, {n_host} HOST + {n_disk} DISK "
        f"on the sharded placement; sharded stats {sstats}")
    check(n_host + n_disk > 0, "mesh: the fixed batch has no cold ids")
    check(sstats["exchanges"] > 0, "mesh: no alltoall exchange ran")
    for k, (a, b, h) in enumerate(zip(rows_s, rows_1, hops)):
        a, b = np.asarray(a), np.asarray(b)
        check(np.array_equal(a, b),
              f"mesh: hop {k} sharded rows differ from the one-chip store")
        check(np.array_equal(a, rows_of(feats, h)),
              f"mesh: hop {k} sharded rows differ from feats[ids]")
    out_err = check_output(infer_fn(rows_s, hops),
                           [rows_of(feats, h) for h in hops], hops, "mesh")

    engine = ServingEngine({"sharded": sex}, StaticScheduler("sharded"),
                           max_inflight=8, admission="wait")
    n_req = 8
    reqs = list(gen.stream(n_req, seeds_per_request=MAX_BATCH))
    engine.warmup([reqs[0]], rounds=1)
    t0 = time.perf_counter()
    summary = engine.run([[r] for r in reqs]).summary()
    serve_s = time.perf_counter() - t0
    engine.close()
    check(summary["requests"] == n_req and summary["shed"] == 0,
          f"mesh: {summary['requests']} of {n_req} served, "
          f"{summary['shed']} shed")
    check(summary["routed"].get("sharded", 0) == n_req,
          f"mesh: routed {summary['routed']}")
    log(f"mesh: lookup {lookup_s} s, served {n_req} requests in "
        f"{serve_s} s (smoke timings, not a benchmark); output err "
        f"{out_err}")
    return {"world": world, "host_ids": n_host, "disk_ids": n_disk,
            "sharded_stats": sstats, "requests": summary["requests"],
            "out_max_err": out_err}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the sharded path over every device")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.serve import build_stack, use_compile_cache

    cache = use_compile_cache()
    log(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(devices)} device(s); compile cache {cache}")
    t0 = time.perf_counter()
    stack = build_stack(nodes=NODES, avg_degree=EDGES / NODES,
                        d_feat=D_FEAT, fanouts=FANOUTS, hot_frac=0.25,
                        seed=SEED)
    graph, store = stack[0], stack[4]
    log(f"build {time.perf_counter() - t0} s: {graph.num_nodes} nodes, "
        f"{graph.num_edges} edges, d={D_FEAT}, tiers "
        f"{store.plan.tier_counts()}")
    graph_dev = graph.device_arrays()
    try:
        if args.mesh:
            report = {"mesh": mesh_phase(stack, graph_dev)}
        else:
            report = {
                "fused": serve_phase(stack, graph_dev, fuse_aggregate=False),
                "fuse_aggregate": serve_phase(stack, graph_dev,
                                              fuse_aggregate=True)}
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(json.dumps(report, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
