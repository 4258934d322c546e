"""Quiver serving launcher — the paper's end-to-end path on the
executor-graph stack.

    PYTHONPATH=src python -m repro.launch.serve --nodes 20000 --requests 400 \
        --policy latency_preferred

Builds the full stack: synthetic skewed graph → PSGS/FAP metrics → feature
placement → tiered store → per-executor latency calibration → N-way
cost-model router → futures-based serving engine; then reports
throughput/latency. With ``--sharded`` (requires ≥2 devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU) a third,
distributed executor joins the registry: mesh-local sampling + one-sided
sharded feature reads. With repeatable ``--models name=preset`` flags the
engine co-serves several GNNs over the ONE shared store — each model gets
its own calibration and router (per-model PSGS cut-points), requests are
tagged round-robin, and the report breaks down per model. ``--spill-path``
backs the DISK tier with a real ``np.memmap`` spill file and ``--prefetch``
stages predicted cold rows into a device-side buffer so HOST/DISK reads
leave the request critical path (see ``benchmarks/prefetch.py``).
``--gpu-cache`` adds the request-granularity device cache in front of the
cold tiers (``--gpu-cache-rows`` capacity; controller-sized under
``--adaptive`` — see ``benchmarks/flash_crowd.py``). ``--gateway`` puts the
SLO-aware admission gateway in front of the engine: requests carry a
priority class (``--priority interactive|batch|mixed``) and optional
relative deadline (``--deadline-ms``), the queue is ordered by deadline
slack with anti-starvation aging, hopeless requests are shed before they
ever occupy an executor, and ``--telemetry`` prints the streaming
queue-depth/saturation/per-class-latency samples at the end (see
``benchmarks/gateway_soak.py``).
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.core import (Prefetcher, ShardedFeatureStore, TieredFeatureStore,
                        TopologySpec, WorkloadGenerator, compute_fap,
                        compute_psgs, quiver_placement)
from repro.graph import power_law_graph
from repro.models.gnn_basic import sage_init, sage_layered
from repro.serving import (AdaptiveConfig, AdaptiveController,
                           CostModelRouter, DeviceExecutor, FrequencySketch,
                           GatewayConfig, HostExecutor, MicroBatcher,
                           ModelRegistry, ServingEngine, ServingGateway,
                           ShardedExecutor, StaticScheduler,
                           build_model_entry, calibrate_executors)

# checkout root (src/repro/launch/serve.py → four levels up)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it itself),
    else the fixed ``.jax_cache`` at the checkout root — a path that never
    moves, so a later run finds what an earlier one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# --models presets: hidden layer widths of the GraphSAGE variant each model
# serves (all share the graph, feature store and samplers — only the model
# compute differs, which is exactly what per-model calibration captures)
MODEL_PRESETS = {
    "sage-small": (64, 64),
    "sage-base": (128, 128),
    "sage-wide": (256, 256),
    "sage-deep": (128, 128, 128),
}


def make_infer_fn(d_feat: int, hidden: tuple[int, ...],
                  fanouts: tuple[int, ...], seed: int = 0):
    """Jitted GraphSAGE ``infer_fn(hop_feats, hop_ids[, deep_agg])`` with
    the given hidden widths — one per served model. ``deep_agg`` carries
    the innermost hop pre-reduced by the fused gather→aggregate store path
    (``hop_feats`` then omits that hop; masks still cover it via
    ``hop_ids``)."""
    params = sage_init(jax.random.key(seed), [d_feat, *hidden])

    @jax.jit
    def infer_fn(hop_feats, hop_ids, deep_agg=None):
        masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
        return sage_layered(params, hop_feats, fanouts, hop_masks=masks,
                            deep_agg=deep_agg)

    return infer_fn


def build_stack(*, nodes: int, avg_degree: float, d_feat: int,
                fanouts: tuple[int, ...], hot_frac: float, seed: int = 0,
                distribution: str = "degree",
                spill_path: str | None = None):
    graph = power_law_graph(nodes, avg_degree, seed=seed)
    rng = np.random.default_rng(seed + 1)
    feats = rng.normal(size=(nodes, d_feat)).astype(np.float32)

    psgs = compute_psgs(graph, fanouts)
    gen = WorkloadGenerator(nodes, graph.out_degree,
                            distribution=distribution, seed=seed + 2)
    fap = compute_fap(graph, fanouts, seed_prob=gen.p)
    topo = TopologySpec(num_pods=1, devices_per_pod=1,
                        rows_per_device=max(nodes // 4, 64),
                        rows_host=max(nodes // 2, 64),
                        hot_replicate_fraction=hot_frac)
    plan = quiver_placement(fap, topo)
    store = TieredFeatureStore.build(feats, plan, spill_path=spill_path)

    infer_fn = make_infer_fn(d_feat, (128, 128), fanouts, seed)

    return graph, feats, psgs, fap, store, gen, infer_fn


def parse_model_specs(specs: list[str]) -> dict[str, tuple[int, ...]]:
    """``name=preset`` flags → {model name: hidden widths}; raises
    SystemExit on malformed specs, duplicate names or unknown presets."""
    models: dict[str, tuple[int, ...]] = {}
    for spec in specs:
        name, sep, preset = spec.partition("=")
        if not sep or not name:
            raise SystemExit(f"--models expects name=preset, got {spec!r}")
        if name in models:
            raise SystemExit(f"--models: duplicate model name {name!r}")
        if preset not in MODEL_PRESETS:
            raise SystemExit(f"--models: unknown preset {preset!r}; "
                             f"choose from {sorted(MODEL_PRESETS)}")
        models[name] = MODEL_PRESETS[preset]
    return models


def build_sharded_store(graph, feats, fap, *, hot_frac: float = 0.25,
                        spill_dir: str | None = None):
    """Mesh + sharded feature store shared by every model's sharded
    executor (built once — the whole point of co-serving is one copy of
    the feature rows). Exits when the runtime has <2 devices. With
    ``spill_dir`` the DISK-tier rows are split into per-shard
    ``DiskSpillTier`` files (shard = id % world) so each shard's cold
    misses read its own mmap, never a cross-shard one."""
    world = len(jax.devices())
    if world < 2:
        raise SystemExit(
            "--sharded needs ≥2 devices; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = jax.make_mesh((world,), ("x",), axis_types=(AxisType.Auto,))
    # rebuild a placement whose warm tier is sharded over the real mesh;
    # size HBM (hot+warm) to cover every node so the sharded store —
    # which serves only the HBM tiers — is exact for any batch
    topo = TopologySpec(num_pods=1, devices_per_pod=world,
                        rows_per_device=max(-(-graph.num_nodes // world),
                                            64),
                        rows_host=max(graph.num_nodes // 2, 64),
                        hot_replicate_fraction=hot_frac)
    splan = quiver_placement(fap, topo)
    sstore = ShardedFeatureStore.from_tiered(
        TieredFeatureStore.build(feats, splan), mesh, "x",
        spill_dir=spill_dir)
    return mesh, sstore, splan


def build_executors(graph, store, fanouts, infer_fn, psgs, *,
                    num_workers: int, max_batch: int, sharded: bool,
                    feats=None, fap=None, hot_frac: float = 0.25,
                    fused: bool = True, fuse_aggregate: bool = False,
                    sharded_spill_dir: str | None = None):
    """Executor registry: host + device, plus the distributed (sharded)
    executor when requested and the runtime has ≥2 devices. ``fused``
    selects the single-dispatch feature-collection path
    (``store.lookup_hops``); ``False`` keeps the legacy per-hop lookups.
    ``fuse_aggregate`` additionally folds the innermost-hop aggregation
    into the gather (``store.lookup_aggregate``); the sharded executor
    downgrades it with a one-time warning (its store serves whole rows
    only — see the support matrix in ``docs/architecture.md``)."""
    executors = {
        "host": HostExecutor(graph, store, fanouts, infer_fn,
                             capacity=num_workers, psgs_table=psgs,
                             fused=fused, fuse_aggregate=fuse_aggregate),
        "device": DeviceExecutor(graph.device_arrays(), store, fanouts,
                                 infer_fn, max_batch=max_batch,
                                 capacity=num_workers, psgs_table=psgs,
                                 fused=fused, fuse_aggregate=fuse_aggregate),
    }
    if sharded:
        mesh, sstore, splan = build_sharded_store(
            graph, feats, fap, hot_frac=hot_frac,
            spill_dir=sharded_spill_dir)
        executors["sharded"] = ShardedExecutor(
            mesh, "x", graph.device_arrays(), sstore, fanouts, infer_fn,
            max_batch=max_batch, psgs_table=psgs, tier_table=splan.tier,
            fused=fused, fuse_aggregate=fuse_aggregate)
    return executors


def make_prefetcher(args, store, fap, controller, hooks, *, sstore=None):
    """``--prefetch`` wiring shared by the single- and multi-model paths:
    build the cold-tier prefetcher, hand it to the adaptive controller
    (refresh per control step, shared sketch) or — without ``--adaptive`` —
    register it as an engine hook with its own sketch and refresh cadence,
    then stage the offline-FAP prediction before serving starts. With a
    sharded store (``sstore``) a second prefetcher drives its per-shard
    staging buffers from the same score signal, so the mesh path sheds
    host callbacks exactly like the single-host one."""
    if not args.prefetch:
        return None
    pf = Prefetcher(store, budget=args.prefetch_budget,
                    refresh_every=(None if controller is not None
                                   else args.adapt_interval))
    if controller is not None:
        controller.attach_prefetcher(pf)
    else:
        pf.sketch = FrequencySketch(store.plan.tier.shape[0])
        hooks.append(pf)
    staged = pf.refresh(scores=fap)
    print(f"[serve] prefetch: staged {staged} cold rows "
          f"(budget {args.prefetch_budget})")
    if sstore is not None:
        spf = Prefetcher(sstore, budget=args.prefetch_budget,
                         refresh_every=(None if controller is not None
                                        else args.adapt_interval))
        if controller is not None:
            controller.attach_prefetcher(spf)
        else:
            spf.sketch = pf.sketch
            hooks.append(spf)
        sstaged = spf.refresh(scores=fap)
        print(f"[serve] prefetch: staged {sstaged} cold rows across the "
              f"mesh shards (budget {args.prefetch_budget})")
    return pf


def make_gpu_cache(args, store, controller):
    """``--gpu-cache`` wiring shared by the single- and multi-model paths:
    put a request-granularity device cache in front of the store's cold
    tiers (``--gpu-cache-rows`` capacity). With ``--adaptive`` it shares
    the controller's frequency sketch — eviction is frequency-weighted and
    the control step resizes the capacity from the measured cold working
    set; without it the capacity stays fixed and eviction is plain CLOCK."""
    if not args.gpu_cache:
        return None
    from repro.core import GPUFeatureCache
    cache = GPUFeatureCache.for_store(
        store, args.gpu_cache_rows,
        sketch=controller.sketch if controller is not None else None)
    store.attach_cache(cache)
    print(f"[serve] gpu-cache: {args.gpu_cache_rows} rows in front of the "
          f"cold tiers"
          + (" (controller-sized)" if controller is not None else ""))
    return cache


def make_gateway(args, engine, controller):
    """``--gateway`` wiring shared by the single- and multi-model paths:
    put the SLO-aware admission gateway in front of the engine and — with
    ``--adaptive`` — hand it to the controller so each control step tunes
    the admission window (``queue_limit``) from observed saturation and
    deadline sheds."""
    if not args.gateway:
        return None
    gw = ServingGateway(engine,
                        config=GatewayConfig(queue_limit=args.gateway_queue))
    if controller is not None:
        controller.attach_gateway(gw)
    print(f"[serve] gateway: queue_limit={args.gateway_queue}, "
          f"priority mix {args.priority!r}"
          + (f", deadline {args.deadline_ms:.0f} ms"
             if args.deadline_ms is not None else ""))
    return gw


def priority_stream_kwargs(args) -> dict:
    """``--priority`` / ``--deadline-ms`` → ``WorkloadGenerator.stream``
    kwargs: class tags (cycled round-robin for ``mixed``) and the relative
    deadline carried by interactive requests (batch requests stay
    deadline-free so aging — not slack — is what keeps them moving)."""
    if not args.gateway:
        return {}
    dl = args.deadline_ms * 1e-3 if args.deadline_ms is not None else None
    if args.priority == "mixed":
        return {"priorities": ("interactive", "batch"),
                "deadlines": (dl, None)}
    return {"priorities": (args.priority,), "deadlines": (dl,)}


def _serve_and_report(args, engine, psgs, reqs, controller,
                      prefetcher=None, cache=None, gateway=None) -> None:
    """Shared tail of the single- and multi-model launcher paths: warmup,
    then the gateway path (per-request SLO admission), the optional
    micro-batched stream (with ``--adapt-micro`` attachment) or pre-formed
    batches, then the JSON report."""
    engine.warmup([reqs[0]])
    if gateway is not None:
        metrics = gateway.serve(reqs)
        print("[serve] gateway:", json.dumps(gateway.report()))
        if args.telemetry:
            samples = gateway.telemetry_samples()
            print(f"[serve] telemetry: {len(samples)} samples, last 5:")
            for s in samples[-5:]:
                print("  ", json.dumps(s))
    elif args.micro_batch > 0:
        # stream path: per-request ingest, then the PSGS-aware coalescing
        # stage feeds the fused gather super-batches under its deadline
        from repro.core import DynamicBatcher
        micro = MicroBatcher(deadline_s=args.micro_deadline_ms * 1e-3,
                             max_seeds=args.micro_batch, psgs_table=psgs)
        if args.adapt_micro and controller is not None:
            # auto-tuning nudges the stage of the first model on the stream
            # (serve_stream clones one per further model)
            controller.attach_micro(micro)
        metrics = engine.serve_stream(
            reqs, DynamicBatcher(deadline_s=0.0, max_batch=1), micro=micro)
        print(f"[serve] micro-batching: {micro.emitted} super-batches, "
              f"{micro.coalesced} coalesced, final bounds "
              f"max_seeds={micro.max_seeds} "
              f"deadline_ms={micro.deadline_s * 1e3:.2f}")
    else:
        metrics = engine.run([[r] for r in reqs])
    print(json.dumps(metrics.summary(), indent=2))
    if controller is not None:
        print("[serve] adaptation:", json.dumps(controller.report()))
    if prefetcher is not None:
        print("[serve] prefetch:", json.dumps(prefetcher.report()))
    if cache is not None:
        print("[serve] gpu-cache:", json.dumps(cache.report()))


def serve_multi_model(args, fanouts, graph, psgs, fap, store, gen) -> None:
    """The ``--models`` path: one engine, one shared store, N models.

    Per model: its own ``infer_fn`` (preset hidden widths), executor set
    over the shared store, calibration, and router — so each model gets its
    own PSGS cut-point. Requests are tagged round-robin across the models;
    admission stays global; the report breaks down per model.
    """
    specs = parse_model_specs(args.models)
    order = np.argsort(psgs)
    cal_batches = [order[int(q * graph.num_nodes):][:args.batch]
                   .astype(np.int64) for q in np.linspace(0.05, 0.95, 8)]
    registry = ModelRegistry()
    for i, (name, hidden) in enumerate(specs.items()):
        infer = make_infer_fn(args.d_feat, hidden, fanouts, seed=i)
        entry = build_model_entry(
            name, graph=graph, store=store, fanouts=fanouts, infer_fn=infer,
            psgs_table=psgs, policy=args.policy, capacity=args.workers,
            max_batch=args.batch, fused=args.fused, rng_seed=i,
            calibration_batches=cal_batches)
        registry.add(entry)
        cut = entry.router.crossover("host", "device")
        print(f"[serve] model {name!r} ({'x'.join(map(str, hidden))}): "
              f"host/device PSGS cut-point {cut:.1f}")

    hooks = []
    controller = None
    if args.adaptive:
        controller = AdaptiveController(
            graph, fanouts, store, registry.routers(), psgs_table=psgs,
            config=AdaptiveConfig(interval_batches=args.adapt_interval,
                                  rows_per_step=args.adapt_rows,
                                  drift_threshold=args.drift_threshold))
        hooks.append(controller)
    prefetcher = make_prefetcher(args, store, fap, controller, hooks)
    cache = make_gpu_cache(args, store, controller)
    engine = ServingEngine(registry, max_inflight=args.max_inflight,
                           admission=args.admission, hooks=hooks)
    gateway = make_gateway(args, engine, controller)
    reqs = list(gen.stream(args.requests, seeds_per_request=args.batch,
                           models=list(specs),
                           **priority_stream_kwargs(args)))
    _serve_and_report(args, engine, psgs, reqs, controller, prefetcher,
                      cache, gateway)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--nodes", type=int, default=20000)
    p.add_argument("--avg-degree", type=float, default=12.0)
    p.add_argument("--d-feat", type=int, default=128)
    p.add_argument("--fanouts", default="10,5")
    p.add_argument("--requests", type=int, default=300)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--policy", default="latency_preferred",
                   choices=["cpu_preferred", "gpu_preferred",
                            "latency_preferred", "throughput_preferred",
                            "host_only", "device_only"])
    p.add_argument("--hot-frac", type=float, default=0.25)
    p.add_argument("--sharded", action="store_true",
                   help="register the distributed executor (needs ≥2 devices)")
    p.add_argument("--max-inflight", type=int, default=64,
                   help="admission window: outstanding batches")
    p.add_argument("--admission", default="wait", choices=["wait", "shed"],
                   help="behavior when the admission window is full")
    p.add_argument("--models", action="append", default=None,
                   metavar="NAME=PRESET",
                   help="co-serve a named model from a preset (repeatable; "
                        f"presets: {sorted(MODEL_PRESETS)}). All models "
                        "share the graph + feature store; each gets its own "
                        "calibration, router and metrics. Omit for the "
                        "single-model path.")
    p.add_argument("--adaptive", action="store_true",
                   help="enable the online workload-adaptation loop: live "
                        "FAP re-placement + router drift refit")
    p.add_argument("--adapt-micro", action="store_true",
                   help="let the adaptive controller auto-tune the micro-"
                        "batcher deadline/max_seeds toward the measured "
                        "latency-curve knee (needs --adaptive and "
                        "--micro-batch > 0)")
    p.add_argument("--adapt-interval", type=int, default=32,
                   help="control period in completed batches")
    p.add_argument("--adapt-rows", type=int, default=64,
                   help="max feature rows migrated per control step")
    p.add_argument("--drift-threshold", type=float, default=0.25,
                   help="relative latency-curve drift that triggers a "
                        "router refit")
    p.add_argument("--fused", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="fused feature collection (cross-hop dedup + one "
                        "tiered_gather dispatch); --no-fused keeps the "
                        "legacy per-hop store lookups")
    p.add_argument("--fuse-aggregate", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="fold the innermost-hop aggregation into the "
                        "gather dispatch (gather_aggregate kernel; the "
                        "dense neighbor tensor is never materialized)")
    p.add_argument("--micro-batch", type=int, default=0,
                   help="coalesce requests into gather-friendly "
                        "super-batches of up to this many seeds before "
                        "admission (0 = off)")
    p.add_argument("--micro-deadline-ms", type=float, default=4.0,
                   help="max milliseconds a request may wait in the "
                        "micro-batching stage")
    p.add_argument("--prefetch", action="store_true",
                   help="stage predicted cold-tier (HOST/DISK) rows into a "
                        "device-side buffer off the critical path; lookups "
                        "resolve staged ids from device memory and only "
                        "fall back to the synchronous host callback on a "
                        "prefetch miss. Refreshed per control step with "
                        "--adaptive, else every --adapt-interval batches.")
    p.add_argument("--prefetch-budget", type=int, default=1024,
                   help="max cold rows staged per prefetch refresh "
                        "(device staging-buffer size)")
    p.add_argument("--gpu-cache", action="store_true",
                   help="request-granularity device cache in front of the "
                        "cold tiers: cold rows are fetched from host/disk "
                        "at most once per residency, repeats are HBM "
                        "gathers. With --adaptive the controller sizes the "
                        "capacity from the measured cold working set.")
    p.add_argument("--gpu-cache-rows", type=int, default=2048,
                   help="device-cache row capacity (initial capacity under "
                        "--adaptive)")
    p.add_argument("--gateway", action="store_true",
                   help="SLO-aware admission gateway in front of the "
                        "engine: priority classes, deadline-slack queue "
                        "ordering with anti-starvation aging, and "
                        "shed-before-dispatch for hopeless requests")
    p.add_argument("--gateway-queue", type=int, default=256,
                   help="gateway admission-queue depth bound (tuned live "
                        "under --adaptive)")
    p.add_argument("--priority", default="batch",
                   choices=["interactive", "batch", "mixed"],
                   help="priority class tagged on the request stream "
                        "(mixed = alternating interactive/batch; needs "
                        "--gateway)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="relative deadline carried by interactive requests "
                        "(mixed keeps batch requests deadline-free; needs "
                        "--gateway)")
    p.add_argument("--telemetry", action="store_true",
                   help="print the gateway's streaming telemetry "
                        "(queue depth, saturation, per-class latency "
                        "percentiles) after serving (needs --gateway)")
    p.add_argument("--spill-path", default=None,
                   help="write DISK-tier rows to an np.memmap spill file at "
                        "this path (the real cold store); omit to keep them "
                        "in host memory")
    p.add_argument("--sharded-spill-dir", default=None,
                   help="directory for the sharded store's per-shard "
                        "DiskSpillTier files (shard = id %% world); omit to "
                        "serve sharded cold misses from the tiered source "
                        "store (needs --sharded)")
    args = p.parse_args()
    fanouts = tuple(int(x) for x in args.fanouts.split(","))
    if args.adapt_micro and not (args.adaptive and args.micro_batch > 0):
        raise SystemExit("--adapt-micro needs --adaptive and "
                         "--micro-batch > 0")
    if not args.gateway and (args.priority != "batch" or args.telemetry
                             or args.deadline_ms is not None):
        raise SystemExit("--priority/--deadline-ms/--telemetry need "
                         "--gateway")
    if args.gateway and args.micro_batch > 0:
        raise SystemExit("--gateway dispatches per request (admission "
                         "ordering is the point); drop --micro-batch")
    if args.sharded_spill_dir is not None and not args.sharded:
        raise SystemExit("--sharded-spill-dir needs --sharded")
    print(f"[serve] compile cache: {use_compile_cache()}")

    graph, feats, psgs, fap, store, gen, infer_fn = build_stack(
        nodes=args.nodes, avg_degree=args.avg_degree, d_feat=args.d_feat,
        fanouts=fanouts, hot_frac=args.hot_frac, spill_path=args.spill_path)
    print(f"[serve] graph: {graph.num_nodes} nodes / {graph.num_edges} edges;"
          f" tiers: {store.plan.tier_counts()}"
          + (f"; spill: {args.spill_path}" if args.spill_path else ""))

    static_policy = args.policy in ("host_only", "device_only")
    if args.models:
        if static_policy:
            raise SystemExit("--models needs a cost-model policy "
                             "(per-model routing is the point)")
        serve_multi_model(args, fanouts, graph, psgs, fap, store, gen)
        return
    if args.sharded and static_policy:
        print("[serve] note: static policy can never route to the sharded "
              "executor; skipping its construction")
    executors = build_executors(graph, store, fanouts, infer_fn, psgs,
                                num_workers=args.workers,
                                max_batch=args.batch,
                                sharded=args.sharded and not static_policy,
                                feats=feats, fap=fap,
                                hot_frac=args.hot_frac, fused=args.fused,
                                fuse_aggregate=args.fuse_aggregate,
                                sharded_spill_dir=args.sharded_spill_dir)
    print(f"[serve] executors: {sorted(executors)}")

    if static_policy:
        router = StaticScheduler("host" if args.policy == "host_only"
                                 else "device")
    else:
        # calibration (paper Fig. 6), generalized to every registered
        # executor: measure across the PSGS range, fit avg+tail curves
        batches = []
        order = np.argsort(psgs)
        for q in np.linspace(0.05, 0.95, 8):
            seeds = order[int(q * graph.num_nodes):][:args.batch]
            batches.append(seeds.astype(np.int64))
        curves = calibrate_executors(executors, batches, psgs, repeats=2)
        router = CostModelRouter.from_curves(psgs, curves, args.policy,
                                             executors=executors)
        mid = float(np.median(psgs)) * args.batch
        ests = {n: router.estimate(n, mid) * 1e3 for n in router.names}
        print(f"[serve] calibrated est @median-batch (ms): "
              f"{ {k: round(v, 2) for k, v in ests.items()} }")

    hooks = []
    controller = None
    if args.adaptive:
        controller = AdaptiveController(
            graph, fanouts, store,
            router if not static_policy else None, psgs_table=psgs,
            config=AdaptiveConfig(interval_batches=args.adapt_interval,
                                  rows_per_step=args.adapt_rows,
                                  drift_threshold=args.drift_threshold))
        hooks.append(controller)
    prefetcher = make_prefetcher(
        args, store, fap, controller, hooks,
        sstore=getattr(executors.get("sharded"), "sstore", None))
    cache = make_gpu_cache(args, store, controller)
    engine = ServingEngine(executors, router,
                           max_inflight=args.max_inflight,
                           admission=args.admission, hooks=hooks)
    gateway = make_gateway(args, engine, controller)
    reqs = list(gen.stream(args.requests, seeds_per_request=args.batch,
                           **priority_stream_kwargs(args)))
    _serve_and_report(args, engine, psgs, reqs, controller, prefetcher,
                      cache, gateway)


if __name__ == "__main__":
    main()
