"""Repo-specific quiverlint configuration: the invariant registries.

This file is the single place where the serving stack's concurrency and
tracing contracts are written down as data (docs/invariants.md is the
prose version). Adding a guarded field, a hot-path root, or a stats
class here immediately puts it under enforcement.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

from quiverlint import (callback_budget, docs_pass, lock_discipline,
                        schema_sync, trace_safety)

PASSES = {
    "lock": lock_discipline.run,
    "trace": trace_safety.run,
    "callback": callback_budget.run,
    "schema": schema_sync.run,
    "docs": docs_pass.run,
}


@dataclasses.dataclass
class SchemaSpec:
    schema_file: str = "src/repro/core/feature_store.py"
    schema_const: str = "STATS_SCHEMA"
    store_class: str = "TieredFeatureStore"
    cache_class: str = "GPUFeatureCache"
    # classes whose `self.stats = {...}` declaration must match their
    # `self.stats["key"]` uses exactly
    stats_classes: tuple = (
        ("core/gpu_cache.py", "GPUFeatureCache"),
        ("core/prefetch.py", "Prefetcher"),
        ("serving/adaptive.py", "AdaptiveController"),
        ("core/feature_store.py", "ShardedFeatureStore"),
        ("serving/gateway.py", "ServingGateway"),
    )
    # auxiliary schema constants: (file suffix, constant, stats class or
    # None, doc marker). Each constant's keys must match the table between
    # `<!-- quiverlint:<marker> -->` markers in marker_doc; with a stats
    # class named, that class's `self.stats` declaration must equal the
    # constant exactly (the constant is the class's published schema).
    aux_schemas: tuple = (
        ("serving/gateway.py", "GATEWAY_SCHEMA", "ServingGateway",
         "gateway-schema"),
        ("serving/gateway.py", "TELEMETRY_SAMPLE_SCHEMA", None,
         "telemetry-schema"),
        ("serving/engine.py", "CLASS_SAMPLE_SCHEMA", None, "class-schema"),
        ("core/feature_store.py", "SHARDED_STATS_SCHEMA",
         "ShardedFeatureStore", "sharded-schema"),
    )
    marker_doc: str = "docs/invariants.md"

    def doc_files(self, root: Path) -> list[Path]:
        return [root / "README.md", *sorted((root / "docs").glob("*.md"))]


@dataclasses.dataclass
class DocsSpec:
    # Public serving API surface whose docstrings are load-bearing
    # (referenced from docs/architecture.md). A bare class name means
    # "class docstring + every public method"; "Class.method" pins
    # specific methods only.
    api: dict = dataclasses.field(default_factory=lambda: {
        "src/repro/serving/engine.py": ["ServingEngine", "MicroBatcher"],
        "src/repro/serving/executors.py": ["Executor", "BaseExecutor",
                                           "HostExecutor", "DeviceExecutor",
                                           "ShardedExecutor"],
        "src/repro/serving/router.py": ["CostModelRouter"],
        "src/repro/serving/registry.py": ["ModelRegistry", "ModelEntry"],
        "src/repro/serving/adaptive.py": ["AdaptiveController",
                                          "FrequencySketch"],
        "src/repro/serving/gateway.py": ["ServingGateway", "GatewayConfig"],
        "src/repro/testing/clock.py": ["FakeClock"],
        "src/repro/core/feature_store.py": [
            "TieredFeatureStore.lookup", "TieredFeatureStore.lookup_hops",
            "TieredFeatureStore.lookup_aggregate",
            "TieredFeatureStore.swap_assignments",
            "TieredFeatureStore.publish_stage",
            "TieredFeatureStore.promote_misses", "DiskSpillTier",
            "ShardedFeatureStore.lookup", "ShardedFeatureStore.lookup_hops",
            "ShardedFeatureStore.publish_stage",
            "ShardedFeatureStore.read_cold_rows"],
        "src/repro/core/prefetch.py": ["Prefetcher"],
        "src/repro/core/gpu_cache.py": ["GPUFeatureCache"],
    })

    def md_files(self, root: Path) -> list[Path]:
        return [root / "README.md", *sorted((root / "docs").glob("*.md"))]


@dataclasses.dataclass
class Config:
    root: Path
    # files the code passes (lock/trace/callback/schema) analyze
    code_globs: list = dataclasses.field(default_factory=lambda: [
        "src/repro/**/*.py", "benchmarks/*.py", "examples/*.py"])

    # -- lock-discipline: (class, field) -> lock attribute ---------------
    # The copy-on-write publication protocol (docs/invariants.md#locks):
    # arrays are REPLACED never mutated, readers snapshot under the same
    # lock the publisher holds.
    guarded_fields: dict = dataclasses.field(default_factory=lambda: {
        "TieredFeatureStore": {
            # migration snapshot — published atomically by swap_assignments
            "hot": "_mig_lock", "warm": "_mig_lock", "host": "_mig_lock",
            "disk": "_mig_lock", "tier_t": "_mig_lock",
            "slot_t": "_mig_lock", "owner_t": "_mig_lock",
            "_stage": "_mig_lock", "cache": "_mig_lock",
            "migrated_rows": "_mig_lock",
            # dispatch accounting
            "stats": "_stats_lock", "_disk_miss_counts": "_stats_lock",
            "promoted_rows": "_stats_lock",
        },
        "GPUFeatureCache": {
            "_rows": "_lock", "_slot_of": "_lock", "_node_of": "_lock",
            "_ref": "_lock", "_hand": "_lock", "_free": "_lock",
            "stats": "_lock", "capacity": "_lock",
        },
        "Prefetcher": {
            "stats": "_lock", "_inflight": "_lock", "_error": "_lock",
            "_last_refresh_t": "_lock",
        },
        "ServingGateway": {
            # one condition guards all gateway state (docstring: the pump
            # re-entrancy flags, queue, counters and telemetry ring move
            # together)
            "stats": "_cv", "_queue": "_cv", "_seq": "_cv",
            "_gw_inflight": "_cv", "_pump_active": "_cv",
            "_pump_again": "_cv", "_telemetry": "_cv",
            "_last_sample_t": "_cv",
        },
        "ServingEngine": {
            "_error": "_lock", "_metrics": "_lock",
            "_inflight_batches": "_acct",
        },
        "AdaptiveController": {
            "samples": "_lock", "stats": "_lock", "_psgs_seen": "_lock",
            "_seeds_seen": "_lock", "_since_step": "_lock",
        },
        "FrequencySketch": {
            "counts": "_lock", "total_observed": "_lock",
        },
        "ShardedFeatureStore": {
            "stats": "_stats_lock",
            # staging snapshot — published atomically by publish_stage
            "_stage": "_stage_lock",
        },
    })
    # methods allowed to touch guarded fields lock-free (besides __init__):
    # documented lock-held-only helpers and build/teardown paths that run
    # before the object is shared
    lock_exempt_methods: dict = dataclasses.field(default_factory=lambda: {
        "GPUFeatureCache": {"_evict_slot"},  # called with _lock held only
        # swap_assignments is the designated single-publisher migration
        # helper: it reads pre-publish state lock-free by design (copy-on-
        # write — new arrays are built off-lock, published atomically under
        # _mig_lock; publisher serialization is the controller's _step_lock)
        "TieredFeatureStore": {"build", "swap_assignments"},
        "ShardedFeatureStore": {"build"},
        # called with _cv held only (documented lock-held-only helpers)
        "ServingGateway": {"_select_locked", "_pop_stale_locked"},
    })

    # -- trace-safety -----------------------------------------------------
    trace_wrappers: frozenset = frozenset({
        "jax.jit", "jit", "shard_map", "jax.shard_map",
        "pl.pallas_call", "pallas_call", "jax.pmap", "pmap",
    })
    np_aliases: frozenset = frozenset({"np", "numpy", "onp"})

    # -- callback-budget --------------------------------------------------
    callback_names: frozenset = frozenset({"io_callback", "pure_callback"})
    # steady-state hot path entry points (qualnames)
    hot_path_roots: frozenset = frozenset({
        "TieredFeatureStore.lookup", "TieredFeatureStore.lookup_hops",
        "TieredFeatureStore.lookup_aggregate",
        "ShardedFeatureStore.lookup", "ShardedFeatureStore.lookup_hops",
        "GPUFeatureCache.query",
        "BaseExecutor.submit", "BaseExecutor._collect",
        "HostExecutor.process", "DeviceExecutor.process",
        "ShardedExecutor.process",
    })
    # the one designated host-fetch fallback
    callback_gateways: frozenset = frozenset({
        "TieredFeatureStore._host_fetch",
    })
    # designated host-data routes that must stay plain numpy: each must
    # resolve, must NOT contain a direct io_callback/pure_callback, and
    # the hot-path BFS stops at them (they are the boundary where device
    # code hands cold ids to the host tiers)
    fetch_gateways: frozenset = frozenset({
        "TieredFeatureStore.read_cold_rows",
        "ShardedFeatureStore.read_cold_rows",
    })
    # roots that must never reach the listed qualnames even transitively:
    # the sharded hot path resolves cold rows through read_cold_rows (its
    # host callback budget is zero by construction — misses merge on the
    # host side of the shard_map, never via the tiered io_callback gateway)
    restricted_roots: dict = dataclasses.field(default_factory=lambda: {
        "ShardedFeatureStore.lookup": ("TieredFeatureStore._host_fetch",),
        "ShardedFeatureStore.lookup_hops": (
            "TieredFeatureStore._host_fetch",),
    })

    schema: SchemaSpec = dataclasses.field(default_factory=SchemaSpec)
    docs: DocsSpec = dataclasses.field(default_factory=DocsSpec)


def build(root: Path) -> Config:
    return Config(root=root)
