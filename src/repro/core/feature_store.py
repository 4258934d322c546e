"""Tiered feature store + one-sided read engine (paper §5.3, TPU-native).

The paper's engine issues zero-copy one-sided reads (UVA / RDMA) from GPU
kernels. On TPU the equivalent is to keep the whole hot/warm path inside one
XLA program so no host mediation happens at all:

  HOT   rows live replicated in every chip's HBM → local gather.
  WARM  rows are node-sharded across chips → fetched with an explicit
        ``shard_map`` exchange (our one-sided read): either
        (a) ``allgather_ids + local gather + reduce_scatter`` (robust for small
            request vectors), or
        (b) capacity-bounded ``all_to_all`` with owner-sorted ids (moves only
            requested rows — the RDMA-read analogue; skew overflow spills to
            the host path, like a cache miss).
  HOST  rows are fetched with ``jax.experimental.io_callback`` (PCIe analogue).
  DISK  rows live in an mmap-backed spill tier (:class:`DiskSpillTier` — an
        ``np.memmap`` file written once at :meth:`TieredFeatureStore.build`
        plus a copy-on-write overlay for migrated rows) and resolve to the
        real feature rows through the same host callback; spill reads and
        critical-path misses are tracked per row, and hot DISK rows can be
        promoted up via :meth:`TieredFeatureStore.promote_misses` (swap-based,
        the existing migration machinery).

Cold-tier accesses can additionally be taken off the critical path entirely
by a :class:`~repro.core.prefetch.Prefetcher`: it stages predicted HOST/DISK
rows into a device-side staging buffer published through
:meth:`TieredFeatureStore.publish_stage`; ``lookup``/``lookup_hops`` resolve
staged ids from device memory and fall back to the synchronous host callback
only on a prefetch miss (hits and misses are counted in the dispatch stats).

The paper's address-sort/TLB optimization survives as: ids are deduplicated
(``fixed_size_unique``) and sorted before every gather/exchange, which both
shrinks collective payloads and improves gather locality.

Fused feature collection (serving hot path): :meth:`TieredFeatureStore.
lookup_hops` collapses the per-hop ``[store.lookup(h) for h in hops]``
pattern into ONE pipeline — concatenate all hops, deduplicate ids once
across hops, do a single address-sorted gather over the device-resident
HOT/WARM tiers (dispatching the Pallas ``tiered_gather`` kernel) plus a
single host callback for the HOST/DISK tiers, then scatter rows back per
hop. For an L-layer sample this replaces 2·(L+1) device gathers and (L+1)
host round-trips with 1 + 1, and the cross-hop dedup shrinks the gathered
row count (hop frontiers overlap heavily on skewed graphs).
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import tracing
from repro.core.placement import (PlacementPlan, TIER_DISK, TIER_HOST,
                                  TIER_HOT, TIER_WARM)
from repro.graph.sampler import fixed_size_unique
from repro.kernels.gather_aggregate.ops import gather_aggregate
from repro.kernels.tiered_gather.kernel import LANES
from repro.kernels.tiered_gather.ops import tiered_gather


# Canonical stats schema for TieredFeatureStore dispatch accounting — THE
# single source of truth. Tests import it (tests/test_prefetch.py,
# tests/test_metrics.py), docs/invariants.md tables it, and quiverlint's
# schema-sync pass cross-checks every producer and doc against it.
STATS_SCHEMA: tuple = (
    "lookup_calls", "fused_calls", "fused_aggregates", "device_gathers",
    "host_fetches", "host_reads", "disk_misses", "spill_reads",
    "prefetch_hits", "prefetch_misses", "cache_hits", "cache_misses",
    "cache_evictions", "host_fetch_traces")


def _new_stats() -> dict[str, int]:
    """Dispatch accounting shared by both lookup paths (benchmark signals:
    ``benchmarks/fused_gather.py`` reports the per-request dispatch
    reduction, ``benchmarks/prefetch.py`` the critical-path host-callback
    reduction). The schema is ``STATS_SCHEMA`` above — new counters are
    added there, documented in ``docs/invariants.md``, and picked up by
    the tests automatically:

      lookup_calls / fused_calls   per-hop vs fused lookup entries
      fused_aggregates             ``lookup_aggregate`` entries: samples
                                   whose innermost-hop aggregation was
                                   folded into the gather dispatch
      device_gathers               tiered_gather / gather_aggregate
                                   dispatches (HOT/WARM)
      host_fetches                 synchronous ``io_callback`` round-trips
                                   actually issued (a lookup whose cold rows
                                   are all staged — or that has none —
                                   issues zero)
      host_reads                   blocking device→host reads a lookup
                                   issued (``_read``: the cold-path checks,
                                   the cache probe, ``lookup_aggregate``'s
                                   segment build)
      disk_misses                  DISK-tier rows resolved synchronously on
                                   the lookup critical path
      spill_reads                  rows read from the DISK spill tier by any
                                   path (critical-path misses + prefetch)
      prefetch_hits                cold rows resolved from the device-side
                                   staging buffer (no host round-trip)
      prefetch_misses              cold rows that fell back to the host
                                   callback while a stage was published
      cache_hits                   cold rows served straight from the
                                   attached device cache (tier dispatch
                                   skipped entirely)
      cache_misses                 cold rows that missed the device cache
                                   and flowed through the tier path (then
                                   admitted on return)
      cache_evictions              resident cache rows displaced by those
                                   admissions
      host_fetch_traces            traces of the jitted host-fetch program
                                   (one per placement snapshot and id-vector
                                   length; each is followed by a compile)
    """
    return dict.fromkeys(STATS_SCHEMA, 0)


class DiskSpillTier:
    """mmap-backed DISK tier: one spill file + a copy-on-write overlay.

    The backing array is written ONCE (at :meth:`TieredFeatureStore.build`)
    and then only ever read: when ``path`` is given it is an ``np.memmap``
    reopened read-only, so cold rows genuinely live on disk, not in RAM.
    Rows that migrate INTO the disk tier afterwards (demotions from
    :meth:`TieredFeatureStore.swap_assignments`) land in a small dict
    overlay instead of mutating the file — ``copy()`` duplicates only the
    overlay and shares the memmap, which keeps the store's copy-on-write
    snapshot publication cheap and torn-read-free (in-flight lookups hold
    the previous ``DiskSpillTier`` object; the file underneath never
    changes). Indexing (``tier[rows]``) reads the backing store and applies
    the overlay, so callers see one coherent array.
    """

    def __init__(self, base: np.ndarray,
                 overlay: Optional[dict[int, np.ndarray]] = None,
                 path: Optional[str] = None):
        self._base = base
        self._overlay: dict[int, np.ndarray] = dict(overlay or {})
        self.path = path
        self._root = path       # first-generation file; .gN names derive
        self._generation = 0    # from it across compactions

    @staticmethod
    def build(rows: np.ndarray, path: Optional[str] = None) -> "DiskSpillTier":
        """Write the DISK-tier rows. With ``path`` the rows go to an
        ``np.memmap`` spill file (flushed, then reopened read-only); without
        it the backing store is plain host memory (tests / tiny stores)."""
        if path is None:
            return DiskSpillTier(rows)
        mm = np.memmap(path, dtype=rows.dtype, mode="w+", shape=rows.shape)
        mm[:] = rows
        mm.flush()
        del mm  # close the writable map before reopening read-only
        base = np.memmap(path, dtype=rows.dtype, mode="r", shape=rows.shape)
        return DiskSpillTier(base, path=path)

    @property
    def shape(self) -> tuple:
        """Backing-store shape ``(rows, d)`` (overlay rows shadow, never
        extend)."""
        return self._base.shape

    @property
    def dtype(self) -> np.dtype:
        """Row dtype of the backing store."""
        return self._base.dtype

    @property
    def overlay_rows(self) -> int:
        """Rows currently shadowed by post-build migrations."""
        return len(self._overlay)

    def __len__(self) -> int:
        return self._base.shape[0]

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            hit = self._overlay.get(int(idx))
            return hit if hit is not None else np.asarray(self._base[idx])
        idx = np.asarray(idx)
        rows = np.asarray(self._base[idx])  # fancy indexing always copies
        if self._overlay:
            # vectorized membership test: the common case (no overlay hit
            # among the requested slots) costs one np.isin, not a Python
            # loop over every requested row
            keys = np.fromiter(self._overlay, dtype=np.int64,
                               count=len(self._overlay))
            flat = idx.ravel()
            for i in np.flatnonzero(np.isin(flat, keys)):
                rows[i] = self._overlay[int(flat[i])]
        return rows

    def __setitem__(self, idx, vals) -> None:
        """Writes go to the overlay, never to the spill file."""
        idx = np.atleast_1d(np.asarray(idx))
        vals = np.atleast_2d(np.asarray(vals))
        for slot, row in zip(idx.ravel(), vals):
            self._overlay[int(slot)] = np.array(row)

    def copy(self) -> "DiskSpillTier":
        """Copy-on-write duplicate: shares the backing store, copies only
        the overlay (the migration publish path calls this)."""
        dup = DiskSpillTier(self._base, self._overlay, self.path)
        dup._root, dup._generation = self._root, self._generation
        return dup

    @property
    def resident_nbytes(self) -> int:
        """Host-RAM bytes actually held by this tier: the overlay plus —
        only when there is no spill file — the backing array itself (the
        memmap pages live on disk and must not count as resident)."""
        row = int(self._base.itemsize * np.prod(self._base.shape[1:]))
        base = 0 if self.path is not None else int(self._base.nbytes)
        return base + row * len(self._overlay)

    def compact(self) -> "DiskSpillTier":
        """Fold the overlay into a fresh backing store and return it as a
        new tier object (the caller publishes it copy-on-write; in-flight
        snapshots keep reading the old base + overlay).

        With a spill file, the merged rows are written to a new generation
        file ``<path>.gN`` and the previous file is unlinked best-effort
        (POSIX keeps it alive for snapshots still mapping it). This bounds
        the RAM the overlay can accumulate under long-running adaptive
        demotion churn — the store auto-compacts on the migration publish
        path once the overlay outgrows ``len(self) // 8``.
        """
        merged = np.asarray(self)
        if self.path is None:
            return DiskSpillTier(merged)
        new_path = f"{self._root}.g{self._generation + 1}"
        fresh = DiskSpillTier.build(merged, new_path)
        fresh._root = self._root
        fresh._generation = self._generation + 1
        try:
            os.unlink(self.path)
        except OSError:
            pass
        return fresh

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        out = np.array(self._base)
        for slot, row in self._overlay.items():
            out[slot] = row
        return out.astype(dtype) if dtype is not None else out


@dataclasses.dataclass
class TieredFeatureStore:
    """Single-host runtime store (serving engine / tests / benchmarks).

    The distributed (mesh) variant is `ShardedFeatureStore` below; this class
    emulates the tier structure faithfully on one device + host memory, so
    policy benchmarks (Fig. 15/16) exercise the same code paths.
    """

    plan: PlacementPlan
    feat_dim: int
    # The device tiers are lane-padded to a multiple of 128 columns (zeros
    # past feat_dim): the Pallas kernels move rows by DMA, which Mosaic
    # only allows in whole 128-lane tiles. At d=100 that costs 23% more
    # HBM than XLA's own layout of the unpadded table (104 columns on a
    # v5e). Every read slices back to feat_dim.
    hot: jnp.ndarray          # (n_hot, d_pad) — "device HBM, replicated"
    warm: jnp.ndarray         # (warm_total, d_pad) — "device HBM, partitioned"
    host: np.ndarray          # (host_total, d) — host RAM (numpy, off device)
    disk: "DiskSpillTier"     # (rest, d) — mmap-backed spill tier
    tier_t: jnp.ndarray       # (N,) int32 lookup tables (device-resident;
    slot_t: jnp.ndarray       # paper: "feature lookup table" via UVA)
    owner_t: jnp.ndarray      # (N,) global warm owner (pod*G + dev), -1 else
    warm_base: jnp.ndarray    # (world,) row offset of each owner's warm shard
    # Online migration support: every lookup reads one consistent snapshot of
    # (tables, tier arrays); swap_assignments publishes a new snapshot
    # atomically under this lock (copy-on-write — in-flight lookups keep
    # serving from the old snapshot, so serving never pauses or torn-reads).
    _mig_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    migrated_rows: int = 0    # lifetime count of rows moved between tiers
    # Dispatch accounting: how many tier-store gathers / host round-trips
    # each lookup path issued (the fused path's whole point is to shrink
    # these). Guarded by its own lock so hot-path increments never contend
    # with migration publishes.
    stats: dict = dataclasses.field(default_factory=_new_stats, repr=False,
                                    compare=False)
    _stats_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    # Prefetch staging state, published atomically like migrations:
    # (stage_slot, stage_rows) where stage_slot is a host-side (N,) int32
    # table (-1 = unstaged) and stage_rows a device-side (budget, d) buffer.
    _stage: Optional[tuple] = dataclasses.field(default=None, repr=False,
                                                compare=False)
    # Per-node DISK critical-path miss counts (guarded by _stats_lock) —
    # the signal for miss-driven promotion.
    _disk_miss_counts: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)
    promoted_rows: int = 0    # lifetime count of miss-driven DISK promotions
    # Optional request-granularity device cache in front of the cold tiers
    # (GPUFeatureCache): queried before tier dispatch, admitted on return.
    cache: Optional[object] = dataclasses.field(default=None, repr=False,
                                                compare=False)
    # Per-thread count of the device→host reads of the lookup running on
    # that thread; each lookup entry adds it to ``host_reads`` in its one
    # ``_count`` call, so counting reads takes no lock of its own.
    _reads: threading.local = dataclasses.field(
        default_factory=threading.local, repr=False, compare=False)
    # (host, disk, jitted fetch) of the snapshot ``_host_fetch`` last built
    # its program for; replaced whole, so a reader needs no lock
    _fetch_prog: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)
    # Tracing contexts of the host fetches in flight, by the int32 token
    # each hands its program (token 0: tracing off, nothing kept); the
    # callback pops its own entry.
    _fetch_ctx: dict = dataclasses.field(default_factory=dict, repr=False,
                                         compare=False)
    _fetch_tokens: itertools.count = dataclasses.field(
        default_factory=itertools.count, repr=False, compare=False)

    @staticmethod
    def build(features: np.ndarray, plan: PlacementPlan, *,
              spill_path: Optional[str] = None) -> "TieredFeatureStore":
        """Lay the feature matrix out across the four tiers of ``plan``.

        Args:
            features: ``(N, d)`` full feature matrix.
            plan: placement decision (tier/owner/slot per node).
            spill_path: when given, the DISK-tier rows are written to an
                ``np.memmap`` spill file at this path (the real cold store);
                ``None`` keeps them in host memory (small stores / tests).
        """
        n, d = features.shape
        d_pad = -(-d // LANES) * LANES
        topo = plan.topology
        world = topo.num_pods * topo.devices_per_pod
        hot_ids = np.flatnonzero(plan.tier == TIER_HOT)
        hot = np.zeros((max(plan.n_hot, 1), d_pad), features.dtype)
        hot[plan.slot[hot_ids], :d] = features[hot_ids]

        # Warm rows concatenated owner-major: [owner0 rows | owner1 rows | ...]
        owner_global = np.where(
            plan.tier == TIER_WARM,
            np.maximum(plan.pod_owner, 0).astype(np.int64) * topo.devices_per_pod
            + plan.device_owner, -1)
        counts = np.array([(owner_global == w).sum() for w in range(world)],
                          dtype=np.int64)
        base = np.zeros(world, dtype=np.int64)
        np.cumsum(counts[:-1], out=base[1:])
        warm = np.zeros((max(int(counts.sum()), 1), d_pad), features.dtype)
        warm_ids = np.flatnonzero(plan.tier == TIER_WARM)
        warm_rows = base[owner_global[warm_ids]] + plan.slot[warm_ids]
        warm[warm_rows, :d] = features[warm_ids]

        host_ids = np.flatnonzero(plan.tier == TIER_HOST)
        # pod-major host layout
        hcounts = np.zeros(topo.num_pods, dtype=np.int64)
        hbase = np.zeros(topo.num_pods, dtype=np.int64)
        for p in range(topo.num_pods):
            hcounts[p] = ((plan.tier == TIER_HOST)
                          & ((plan.pod_owner == p) | (plan.pod_owner == -1))).sum()
        np.cumsum(hcounts[:-1], out=hbase[1:])
        host = np.zeros((max(int(hcounts.sum()), 1), d), features.dtype)
        hpod = np.maximum(plan.pod_owner[host_ids], 0)
        host[hbase[hpod] + plan.slot[host_ids]] = features[host_ids]

        disk_ids = np.flatnonzero(plan.tier == TIER_DISK)
        disk_rows = np.zeros((max(disk_ids.shape[0], 1), d), features.dtype)
        disk_rows[plan.slot[disk_ids]] = features[disk_ids]
        disk = DiskSpillTier.build(disk_rows, spill_path)

        # Unified slot table pointing into each tier's flat store.
        slot_flat = plan.slot.copy()
        slot_flat[warm_ids] = warm_rows
        slot_flat[host_ids] = hbase[hpod] + plan.slot[host_ids]

        return TieredFeatureStore(
            plan=plan, feat_dim=d,
            hot=jnp.asarray(hot), warm=jnp.asarray(warm), host=host, disk=disk,
            tier_t=jnp.asarray(plan.tier, jnp.int32),
            slot_t=jnp.asarray(slot_flat, jnp.int32),
            owner_t=jnp.asarray(owner_global, jnp.int32),
            warm_base=jnp.asarray(base, jnp.int32),
            _disk_miss_counts=np.zeros(n, dtype=np.int64))

    # -- lookup -------------------------------------------------------------
    def _snapshot(self) -> tuple:
        """Consistent view (hot, warm, host, disk, tier_t, slot_t, stage).
        Arrays are replaced — never mutated — by migration and by stage
        publication, so holding the references is enough to keep serving
        from one coherent placement + staging state."""
        with self._mig_lock:
            return (self.hot, self.warm, self.host, self.disk,
                    self.tier_t, self.slot_t, self._stage)

    def _count(self, **deltas: int) -> None:
        with self._stats_lock:
            for k, v in deltas.items():
                self.stats[k] += v

    def _read(self, x) -> np.ndarray:
        """Blocking device→host copy of ``x``: every such read on the
        lookup path goes through here, so it is counted (``host_reads``)
        and, when tracing, timed as ``store.read``."""
        self._reads.n += 1
        with tracing.span("store.read"):
            return np.asarray(x)

    def reset_stats(self) -> dict[str, int]:
        """Zero the dispatch counters, returning the previous values."""
        with self._stats_lock:
            prev, self.stats = self.stats, _new_stats()
        return prev

    def snapshot_stats(self) -> dict[str, int]:
        """Copy of the dispatch counters WITHOUT resetting them (the
        adaptive controller reads per-interval deltas from this, so it
        must not race benchmark-owned :meth:`reset_stats` windows)."""
        with self._stats_lock:
            return dict(self.stats)

    def attach_cache(self, cache) -> "TieredFeatureStore":
        """Attach (``None`` detaches) a request-granularity device cache
        (:class:`~repro.core.gpu_cache.GPUFeatureCache`) in front of the
        cold tiers: lookups probe it for HOST/DISK ids before tier
        dispatch, serve hits from device memory, and admit misses on
        return. Detaching never changes lookup results — cached rows are
        copies of the exact feature values. Returns the store for
        chaining."""
        with self._mig_lock:
            self.cache = cache
        return self

    def lookup(self, ids: jnp.ndarray, *, include_host: bool = True,
               dedup: bool = True) -> jnp.ndarray:
        """Gather feature rows for one id vector.

        Args:
            ids: ``(M,)`` int node ids; ``-1`` entries are padding and
                resolve to all-zero rows.
            include_host: also resolve HOST/DISK-tier ids through the host
                callback (the PCIe-analogue slow path). When ``False`` those
                rows come back as zeros (device-only probe).
            dedup: deduplicate + sort ids (``fixed_size_unique``) before
                gathering — the paper's TLB/address-sort optimization.

        Returns:
            ``(M, d)`` feature matrix in the input id order, read from one
            consistent placement snapshot (safe under concurrent
            :meth:`swap_assignments`).
        """
        snap = self._snapshot()
        self._reads.n = 0
        if dedup:
            uniq, inv = fixed_size_unique(jnp.asarray(ids, jnp.int32),
                                          int(ids.shape[0]))
            rows = self._cached_unique(uniq, include_host, snap, None,
                                       fused=False)[inv]
        else:
            rows = self._cached_unique(jnp.asarray(ids, jnp.int32),
                                       include_host, snap, None, fused=False)
        self._count(lookup_calls=1, host_reads=self._reads.n)
        return jnp.where((jnp.asarray(ids) >= 0)[:, None], rows, 0.0)

    def lookup_hops(self, hops, *, include_host: bool = True,
                    use_pallas: Optional[bool] = None) -> list[jnp.ndarray]:
        """Fused feature collection for a whole layered sample.

        Collapses the per-hop ``[store.lookup(h) for h in hops]`` pattern
        into one pipeline: concatenate all hop id vectors, deduplicate ids
        ONCE across hops, gather the device-resident HOT/WARM tiers with a
        single address-sorted dispatch of the Pallas ``tiered_gather``
        kernel, resolve HOST/DISK ids with a single host callback, and
        scatter rows back into per-hop order. Output is bit-identical to the
        per-hop path (gathers copy rows; no arithmetic is reordered) and
        reads one consistent placement snapshot for the *entire* sample,
        so it is safe under concurrent :meth:`swap_assignments`.

        Args:
            hops: sequence of id vectors (``hops[0]`` the seeds, ``hops[k]``
                the k-th frontier), each ``(M_k,)`` with ``-1`` padding.
                At least one hop must be non-empty.
            include_host: as in :meth:`lookup`.
            use_pallas: force (``True``) or suppress (``False``) the Pallas
                kernel for the device-tier gather; ``None`` picks it on TPU
                and the jnp reference elsewhere (interpret mode is used for
                the kernel off-TPU, so ``True`` is safe on CPU tests).

        Returns:
            List of ``(M_k, d)`` feature matrices, one per hop, matching
            ``[self.lookup(h) for h in hops]`` bit-for-bit.

        Raises:
            ValueError: if ``hops`` is empty or all hops have zero length.
        """
        with tracing.span("store.lookup_hops"):
            hops_j = [jnp.asarray(h, jnp.int32).reshape(-1) for h in hops]
            sizes = [int(h.shape[0]) for h in hops_j]
            total = sum(sizes)
            if total == 0:
                raise ValueError(
                    "lookup_hops needs at least one non-empty hop")
            snap = self._snapshot()
            self._reads.n = 0
            with tracing.span("store.dedup"):
                ids = (hops_j[0] if len(hops_j) == 1
                       else jnp.concatenate(hops_j))
                uniq, inv = fixed_size_unique(ids, total)
            rows = self._cached_unique(uniq, include_host, snap, use_pallas,
                                       fused=True)
            self._count(fused_calls=1, host_reads=self._reads.n)
            out = jnp.where((ids >= 0)[:, None], rows[inv], 0.0)
            offs = np.concatenate([[0], np.cumsum(sizes)])
            return [out[int(offs[k]):int(offs[k + 1])]
                    for k in range(len(sizes))]

    def lookup_aggregate(self, hops, *, include_host: bool = True,
                         use_pallas: Optional[bool] = None,
                         block_rows: int = 8, block_dim: int = 0):
        """Fused feature collection + innermost-hop segment aggregation.

        The innermost hop is the largest tensor of a layered sample and the
        model consumes it exactly once: layer 1 immediately reduces each
        fan-sized child segment into its parent. This entry point folds that
        reduction into the gather itself with the ``gather_aggregate``
        kernel — child rows stream from the HOT/WARM tier buffers (or the
        pre-resolved cold side-table) straight into per-parent accumulators,
        and the dense ``(n_sampled, d)`` neighbor tensor is never
        materialized. Outer hops ride in the same dispatch as singleton
        segments, so the whole sample still costs ONE device gather.

        Cold (HOST/DISK) ids are resolved *before* the kernel through the
        exact machinery :meth:`lookup_hops` uses — device cache probe,
        staging-buffer hit, then at most one ``_host_fetch`` callback (the
        single ``io_callback`` gateway) — into a compact side-table the
        kernel indexes as tier 2, preserving all dispatch counters and the
        one-gateway invariant.

        Tier-equivalence guarantee: the returned aggregate is bit-identical
        to gathering with :meth:`lookup_hops` and reducing in the model
        (``(child * mask).sum(1)``), regardless of how rows are spread
        across HOT/WARM/HOST/DISK tiers or moved by concurrent
        :meth:`swap_assignments` — gathers copy rows and the fused kernel
        accumulates in the same order over the same values.

        Args:
            hops: sequence of ≥ 2 id vectors (seeds first); the innermost
                hop must have ``len(hops[-2]) * fan`` entries, ``-1``
                padding for absent children.
            include_host: as in :meth:`lookup`; ``False`` makes cold
                children contribute zero rows (they still count toward the
                caller's mask-derived segment sizes, as in the unfused
                path).
            use_pallas: kernel dispatch override, as in :meth:`lookup_hops`.
            block_rows: segment-block height of the fused kernel.
            block_dim: feature-dim tile width (0 → untiled); see the
                ``gather_aggregate`` autotune harness.

        Returns:
            ``(feats, agg_sum)``: ``feats`` the ``(M_k, d)`` feature
            matrices for ``hops[:-1]`` (bit-identical to
            ``lookup_hops(hops)[:-1]``), ``agg_sum`` a
            ``(len(hops[-2]), d)`` matrix of per-parent child-row sums —
            divide by the mask count to finish mean aggregation
            (``models.gnn_basic.sage_layered(deep_agg=...)`` does).

        Raises:
            ValueError: fewer than two hops, or the innermost hop is not a
                whole multiple of the previous hop.
        """
        hops_j = [jnp.asarray(h, jnp.int32).reshape(-1) for h in hops]
        sizes = [int(h.shape[0]) for h in hops_j]
        if len(hops_j) < 2:
            raise ValueError(
                "lookup_aggregate needs seeds plus at least one frontier")
        p, n_inner = sizes[-2], sizes[-1]
        if p == 0 or n_inner == 0 or n_inner % p:
            raise ValueError(
                "innermost hop must be a (P*fan,) frontier of the previous "
                f"hop, got sizes {sizes[-2:]}")
        fan = n_inner // p
        total = sum(sizes)
        snap = self._snapshot()
        self._reads.n = 0
        hot, warm = snap[0], snap[1]
        tier_t, slot_t = snap[4], snap[5]
        ids = jnp.concatenate(hops_j)
        uniq, inv = fixed_size_unique(ids, total)
        uniq_np = self._read(uniq)
        valid_u = uniq_np >= 0
        tier_np = self._read(tier_t)[np.maximum(uniq_np, 0)]
        slot_np = self._read(slot_t)[np.maximum(uniq_np, 0)]
        cold = valid_u & (tier_np >= TIER_HOST)
        cold_idx = np.flatnonzero(cold)
        # per-unique kernel addresses: 0=hot, 1=warm, 2=cold table, 99=skip
        ktier = np.full(total, 99, np.int32)
        ktier[valid_u & (tier_np == TIER_HOT)] = 0
        ktier[valid_u & (tier_np == TIER_WARM)] = 1
        kslot = slot_np.astype(np.int32)
        if include_host and cold_idx.size:
            cold_full = self._cached_unique(uniq, include_host, snap,
                                            use_pallas, fused=True,
                                            cold_only=True)
            # pad the side-table row count to a power of two so the jitted
            # kernel compiles once per bucket, not once per cold count
            kpad = max(1, 1 << (int(cold_idx.size) - 1).bit_length())
            pad_idx = np.zeros(kpad, np.int64)
            pad_idx[:cold_idx.size] = cold_idx
            cold_buf = jnp.pad(cold_full[jnp.asarray(pad_idx)],
                               ((0, 0), (0, hot.shape[1] - self.feat_dim)))
            ktier[cold] = 2
            kslot[cold] = np.arange(cold_idx.size, dtype=np.int32)
        else:
            # device-only probe (or nothing cold): cold children contribute
            # zero rows, exactly like the unfused include_host=False path
            cold_buf = jnp.zeros((1, hot.shape[1]), hot.dtype)
        inner_np = self._read(hops_j[-1])
        inv_np = self._read(inv)
        inv_inner = inv_np[total - n_inner:]
        # segment matrix: one singleton segment per unique id (recovers the
        # outer-hop feature rows from the same dispatch), then one fan-wide
        # segment per innermost-hop parent. -1 children alias the last
        # unique slot via ``inv``, so they are re-masked to 99 here.
        seg_tier = np.full((total + p, fan), 99, np.int32)
        seg_slot = np.zeros((total + p, fan), np.int32)
        seg_tier[:total, 0] = ktier
        seg_slot[:total, 0] = kslot
        seg_tier[total:] = np.where(inner_np < 0, 99,
                                    ktier[inv_inner]).reshape(p, fan)
        seg_slot[total:] = np.where(inner_np < 0, 0,
                                    kslot[inv_inner]).reshape(p, fan)
        self._count(fused_calls=1, fused_aggregates=1, device_gathers=1,
                    host_reads=self._reads.n)
        out = gather_aggregate(jnp.asarray(seg_tier), jnp.asarray(seg_slot),
                               hot, warm, cold_buf, block_rows=block_rows,
                               block_dim=block_dim, use_pallas=use_pallas
                               )[:, :self.feat_dim]
        rows_u = out[:total]
        agg = out[total:]
        outer = ids[: total - n_inner]
        outer_rows = jnp.where((outer >= 0)[:, None],
                               rows_u[inv[: total - n_inner]], 0.0)
        offs = np.concatenate([[0], np.cumsum(sizes[:-1])])
        feats = [outer_rows[int(offs[k]):int(offs[k + 1])]
                 for k in range(len(sizes) - 1)]
        return feats, agg

    def _cached_unique(self, uniq: jnp.ndarray, include_host: bool,
                       snap: tuple, use_pallas: Optional[bool], *,
                       fused: bool, cold_only: bool = False) -> jnp.ndarray:
        """Route one (deduplicated) id vector through the optional device
        cache, then the tier dispatch for whatever remains.

        Cold-tier (HOST/DISK) ids probe the cache first; hits are blanked
        to ``-1`` in the tier path's id vector, so they never touch the
        tier gather or the host callback. Missed rows flow through the
        normal fused/per-hop pipeline and are admitted into the cache on
        return. When EVERY valid id is a cold cache hit the tier gather is
        skipped entirely — ``device_gathers`` is counted here, at the
        dispatch site, so that fast path is visible in the stats (the
        uncached counts are unchanged: 1 per fused call, 2 per plain
        lookup). ``include_host=False`` bypasses the cache: device-only
        probes must keep returning zeros for cold tiers.

        Bit-identity: cached rows are copies of the same feature values
        and migration moves rows with their nodes, so mixing cache hits
        with tier-path rows can never change a lookup result.
        """
        gathers = 0 if cold_only else (1 if fused else 2)
        if cold_only:
            # lookup_aggregate mode: the fused kernel reads HOT/WARM rows
            # itself, so the tier path only resolves the cold remainder
            tier_path = self._cold_unique
        else:
            tier_path = (partial(self._fused_unique, use_pallas=use_pallas)
                         if fused else self._lookup_unique)
        # lock-free single reference read: any published cache (or None) is
        # valid here — cached rows are copies, so bit-identity cannot break
        cache = self.cache  # quiverlint: disable=lock-discipline atomic reference read, any snapshot valid
        if cache is None or not include_host:
            self._count(device_gathers=gathers)
            return tier_path(uniq, include_host, snap)
        uniq_np = self._read(uniq)
        tier_np = self._read(snap[4][jnp.maximum(jnp.asarray(uniq), 0)])
        cold = (uniq_np >= 0) & (tier_np >= TIER_HOST)
        if not cold.any():
            self._count(device_gathers=gathers)
            return tier_path(uniq, include_host, snap)
        values, miss_index, miss_ids = cache.query(
            np.where(cold, uniq_np, -1))
        hit = cold.copy()
        hit[miss_index] = False
        self._count(cache_hits=int(hit.sum()),
                    cache_misses=int(miss_index.size))
        if not ((uniq_np >= 0) & ~hit).any():
            return values        # every valid id was a cold cache hit
        uniq_eff = jnp.where(jnp.asarray(hit), jnp.int32(-1),
                             jnp.asarray(uniq, jnp.int32))
        self._count(device_gathers=gathers)
        rows = tier_path(uniq_eff, include_host, snap)
        out = jnp.where(jnp.asarray(hit)[:, None], values, rows)
        if miss_index.size:
            evicted = cache.replace(miss_ids, out[jnp.asarray(miss_index)])
            self._count(cache_evictions=int(evicted))
        return out

    def _fused_unique(self, uniq: jnp.ndarray, include_host: bool,
                      snap: tuple, use_pallas: Optional[bool]) -> jnp.ndarray:
        """One gather per tier class for a deduplicated id vector: the
        HOT/WARM rows stream through ``tiered_gather`` in ascending
        (tier, slot) order — near-sequential DMAs, the paper's TLB
        optimization — and HOST/DISK rows come from the staging buffer
        (prefetch hit) or one ``_host_fetch`` (miss fallback)."""
        hot, warm, host, disk, tier_t, slot_t, stage = snap
        with tracing.span("store.gather"):
            safe = jnp.maximum(uniq, 0)
            tier = tier_t[safe]
            slot = slot_t[safe]
            # address-sort key: tier-major, slot-minor. Slots are clamped
            # into the device-tier span only for key construction (host-tier
            # slots may exceed it; their gather result is zeros either way),
            # which keeps the key within int32 for any store below ~5e8
            # rows/tier.
            span = jnp.int32(max(int(hot.shape[0]), int(warm.shape[0]), 1))
            key = tier.astype(jnp.int32) * span + jnp.minimum(slot, span - 1)
            order = jnp.argsort(key)
            dev_sorted = tiered_gather(
                tier[order], slot[order], hot, warm,
                use_pallas=use_pallas)[:, :self.feat_dim]
            out = jnp.zeros_like(dev_sorted).at[order].set(dev_sorted)
        if include_host:
            out = self._resolve_cold(uniq, tier, slot, out, host, disk,
                                     stage)
        return jnp.where((uniq >= 0)[:, None], out, 0.0)

    def _cold_unique(self, uniq: jnp.ndarray, include_host: bool,
                     snap: tuple) -> jnp.ndarray:
        """Cold-rows-only tier path for :meth:`lookup_aggregate`: resolve
        HOST/DISK rows through the staging buffer / ``_host_fetch`` gateway
        exactly as the full paths do, but skip the device-tier gather (the
        fused kernel streams HOT/WARM rows straight from the tier buffers).
        Non-cold positions come back as zeros."""
        hot, warm, host, disk, tier_t, slot_t, stage = snap
        safe = jnp.maximum(uniq, 0)
        tier = tier_t[safe]
        slot = slot_t[safe]
        out = jnp.zeros((uniq.shape[0], self.feat_dim), hot.dtype)
        if include_host:
            out = self._resolve_cold(uniq, tier, slot, out, host, disk,
                                     stage)
        return jnp.where((uniq >= 0)[:, None], out, 0.0)

    def _lookup_unique(self, ids: jnp.ndarray, include_host: bool,
                       snap: Optional[tuple] = None) -> jnp.ndarray:
        hot, warm, host, disk, tier_t, slot_t, stage = (
            snap if snap is not None else self._snapshot())
        safe = jnp.maximum(ids, 0)
        tier = tier_t[safe]
        slot = slot_t[safe]
        d = self.feat_dim
        out = jnp.zeros((ids.shape[0], d), hot.dtype)
        out = jnp.where((tier == TIER_HOT)[:, None],
                        hot[jnp.minimum(slot, hot.shape[0] - 1), :d], out)
        out = jnp.where((tier == TIER_WARM)[:, None],
                        warm[jnp.minimum(slot, warm.shape[0] - 1), :d],
                        out)
        if include_host:
            out = self._resolve_cold(ids, tier, slot, out, host, disk,
                                     stage)
        return jnp.where((ids >= 0)[:, None], out, 0.0)

    def _resolve_cold(self, ids: jnp.ndarray, tier: jnp.ndarray,
                      slot: jnp.ndarray, out: jnp.ndarray, host, disk,
                      stage: Optional[tuple]) -> jnp.ndarray:
        """Resolve HOST/DISK-tier rows of one id vector.

        Staged ids (prefetched into the device-side buffer) are gathered
        from device memory — no host round-trip; the rest fall back to the
        synchronous ``_host_fetch`` callback. When every cold id is staged
        (or there are none) the callback is skipped entirely, which is the
        whole point of the prefetcher: zero critical-path host callbacks.
        Hit/miss/disk counters land in the dispatch stats; staged rows are
        bit-identical to the host/disk rows (they are copies of the same
        float values), so this path never changes lookup results.
        """
        with tracing.span("store.cold"):
            ids_np = self._read(ids)
            tier_np = self._read(tier)
            cold = (tier_np >= TIER_HOST) & (ids_np >= 0)
            if not cold.any():
                return out
            miss = cold
            if stage is not None:
                stage_slot, stage_rows = stage
                sslot = stage_slot[np.maximum(ids_np, 0)]
                hit = cold & (sslot >= 0)
                miss = cold & ~hit
                self._count(prefetch_hits=int(hit.sum()),
                            prefetch_misses=int(miss.sum()))
                if hit.any():
                    # full-width gather + where keeps the shapes static (one
                    # compile per id-bucket, like the host path) — a dynamic
                    # hit-index scatter would recompile on every hit count
                    gathered = stage_rows[jnp.asarray(np.maximum(sslot, 0))]
                    out = jnp.where(jnp.asarray(hit)[:, None], gathered, out)
            if miss.any():
                disk_miss = miss & (tier_np == TIER_DISK)
                n_disk = int(disk_miss.sum())
                self._count(host_fetches=1, disk_misses=n_disk,
                            spill_reads=n_disk)
                if n_disk:
                    with self._stats_lock:
                        if self._disk_miss_counts is not None:
                            np.add.at(self._disk_miss_counts,
                                      ids_np[disk_miss], 1)
                # mask the staged positions out of the callback's tier vector
                # so it only gathers the rows that actually missed
                tier_eff = jnp.asarray(np.where(miss, tier_np, -1)
                                       .astype(np.int32))
                rows = self._host_fetch(tier_eff, slot, host, disk)
                out = jnp.where(jnp.asarray(miss)[:, None], rows, out)
            return out

    def _host_fetch(self, tier, slot, host=None, disk=None):
        """PCIe-analogue slow path: host callback, ids sorted by address
        (the paper's TLB optimization) before the gather.

        The callback runs inside one jitted program per placement snapshot
        (its ``host``/``disk`` objects, compared by identity), which JAX
        compiles once per id-vector length; a fetch on a snapshot that is
        not the cached one builds that snapshot's own program, so a lookup
        never reads rows of another snapshot. The request's tracing context
        rides to the callback as an int32 token operand, not a closure."""
        if host is None:
            # one coherent snapshot — reading the two attributes directly
            # could tear across a concurrent migration publish
            _, _, host, disk, _, _, _ = self._snapshot()

        with tracing.span("store.host_fetch"):
            prog, ctxs = self._fetch_prog, self._fetch_ctx
            if prog is None or prog[0] is not host or prog[1] is not disk:
                def cb(tier_np, slot_np, token):
                    # the body runs on a runtime thread: the dispatching
                    # request's context comes back through its token
                    token = int(token)
                    ctx = ctxs.pop(token, None) if token else None
                    with tracing.span("store.callback", ctx=ctx):
                        tier_np = np.asarray(tier_np)
                        slot_np = np.asarray(slot_np)
                        out = np.zeros((tier_np.shape[0], host.shape[1]),
                                       host.dtype)
                        m_h = tier_np == TIER_HOST
                        m_d = tier_np == TIER_DISK
                        # address-sorted gathers
                        for m, store in ((m_h, host), (m_d, disk)):
                            idx = np.flatnonzero(m)
                            if idx.size:
                                order = np.argsort(slot_np[idx])
                                rows = store[slot_np[idx][order]]
                                out[idx[order]] = rows
                        return out

                fetch = jax.jit(lambda tier, slot, token: io_callback(
                    cb, self._fetch_type(tier, host.dtype), tier, slot,
                    token, ordered=False))
                # two racing builds are both this snapshot's; either is kept
                prog = (host, disk, fetch)
                self._fetch_prog = prog
            token = 0
            if tracing.enabled():
                token = next(self._fetch_tokens) % (2**31 - 1) + 1
                ctxs[token] = tracing.current()
            try:
                return prog[2](tier, slot, np.int32(token))
            except BaseException:
                ctxs.pop(token, None)
                raise

    def _fetch_type(self, tier, dtype) -> jax.ShapeDtypeStruct:
        """Result type of the host-fetch program for ``tier``'s length.
        Python calls this only while JAX traces the program (once per
        snapshot and id-vector length), so it counts the traces."""
        self._count(host_fetch_traces=1)
        return jax.ShapeDtypeStruct((tier.shape[0], self.feat_dim), dtype)

    # -- prefetch staging ----------------------------------------------------
    def publish_stage(self, stage_slot: Optional[np.ndarray],
                      stage_rows) -> None:
        """Atomically publish (or clear) the prefetch staging state.

        Args:
            stage_slot: ``(N,)`` int32 host-side table mapping node id →
                row in ``stage_rows`` (``-1`` = unstaged), or ``None`` to
                clear the stage.
            stage_rows: ``(budget, d)`` device-side staging buffer holding
                the prefetched cold rows (ignored when ``stage_slot`` is
                ``None``).

        Published under the migration lock like a placement snapshot:
        in-flight lookups keep resolving against the previous stage, new
        lookups see the new one — never a torn mix.
        """
        stage = None if stage_slot is None else (stage_slot, stage_rows)
        with self._mig_lock:
            self._stage = stage

    def staged_rows(self) -> int:
        """Number of cold rows currently staged on device (0 = no stage)."""
        with self._mig_lock:
            stage = self._stage
        return 0 if stage is None else int((stage[0] >= 0).sum())

    def read_cold_rows(self, ids: np.ndarray) -> np.ndarray:
        """Read the feature rows of ``ids`` for staging, OFF the critical
        path (plain host-side reads, no device round-trip for cold tiers).

        Each row is read from whichever tier currently holds it under one
        consistent snapshot, so a migration racing the prefetcher still
        yields exact values (rows travel with nodes; values never change).
        DISK reads are counted as ``spill_reads``.

        Args:
            ids: ``(K,)`` valid node ids (no ``-1`` padding).

        Returns:
            ``(K, d)`` feature rows in ``ids`` order.
        """
        hot, warm, host, disk, tier_t, slot_t, _ = self._snapshot()
        ids = np.asarray(ids)
        tier = np.asarray(tier_t)[ids]
        slot = np.asarray(slot_t)[ids]
        out = np.zeros((ids.shape[0], self.feat_dim),
                       np.asarray(host).dtype)
        m_host, m_disk = tier == TIER_HOST, tier == TIER_DISK
        if m_host.any():
            out[m_host] = host[slot[m_host]]
        if m_disk.any():
            out[m_disk] = disk[slot[m_disk]]
            self._count(spill_reads=int(m_disk.sum()))
        m_dev = ~(m_host | m_disk)  # raced a promotion: read device tiers
        if m_dev.any():
            hot_np, warm_np = np.asarray(hot), np.asarray(warm)
            for i in np.flatnonzero(m_dev):
                src = hot_np if tier[i] == TIER_HOT else warm_np
                out[i] = src[min(int(slot[i]), src.shape[0] - 1),
                             :self.feat_dim]
        return out

    # -- miss-driven promotion -----------------------------------------------
    def promote_misses(self, *, budget: int = 32, min_misses: int = 1) -> int:
        """Swap the most-missed DISK rows up into the HOST tier.

        Candidates are DISK-tier nodes with at least ``min_misses``
        critical-path misses since the last promotion, hottest first;
        victims are HOST-tier rows with the fewest recorded misses, coldest
        build rank (highest slot) first. Swaps ride the existing
        :meth:`swap_assignments` machinery, so tier counts, capacity and
        the lookup-equivalence invariant are all preserved and concurrent
        lookups keep serving from the previous snapshot.

        Args:
            budget: max node pairs to exchange this call.
            min_misses: miss-count threshold for promotion.

        Returns:
            Number of feature rows moved (``2 *`` pairs swapped), also
            accumulated into :attr:`promoted_rows` / :attr:`migrated_rows`.
        """
        with self._stats_lock:
            if self._disk_miss_counts is None:
                return 0
            counts = self._disk_miss_counts.copy()
        # tier/slot must come from one coherent snapshot: reading them in
        # two separate attribute loads can tear across a migration publish
        # and pair a node's new tier with its old slot
        _, _, _, _, tier_t, slot_t, _ = self._snapshot()
        tier = np.asarray(tier_t)
        cand = np.flatnonzero((tier == TIER_DISK) & (counts >= min_misses))
        hosts = np.flatnonzero(tier == TIER_HOST)
        if not cand.size or not hosts.size:
            return 0
        cand = cand[np.argsort(-counts[cand], kind="stable")][:budget]
        slot = np.asarray(slot_t)
        victims = hosts[np.lexsort((-slot[hosts], counts[hosts]))]
        k = min(cand.size, victims.size)
        pairs = list(zip(cand[:k].tolist(), victims[:k].tolist()))
        moved = self.swap_assignments(pairs)
        with self._stats_lock:
            self._disk_miss_counts[cand[:k]] = 0
            self.promoted_rows += moved
        return moved

    def tier_histogram(self, ids: np.ndarray) -> dict[str, int]:
        ids = np.asarray(ids)
        ids = ids[ids >= 0]
        t = self.plan.tier[ids]
        return {"hot": int((t == TIER_HOT).sum()),
                "warm": int((t == TIER_WARM).sum()),
                "host": int((t == TIER_HOST).sum()),
                "disk": int((t == TIER_DISK).sum())}

    # -- online migration ----------------------------------------------------
    def swap_assignments(self, pairs: list[tuple[int, int]]) -> int:
        """Exchange the complete (tier, slot, owner) assignments — and the
        stored feature rows — of disjoint node pairs, atomically w.r.t.
        concurrent :meth:`lookup` / :meth:`lookup_hops`.

        Each node inherits its partner's placement wholesale, so per-tier
        counts, per-device capacity and the owner-major warm layout are all
        preserved; ``lookup(i)`` returns bit-identical features before,
        during and after the swap (the lookup-equivalence invariant — the
        rows travel with the nodes). New arrays are built copy-on-write and
        published under the migration lock; in-flight lookups keep reading
        the previous snapshot.

        Args:
            pairs: ``(a, b)`` node-id pairs to exchange. Node ids must be
                pairwise disjoint across all pairs.

        Returns:
            Number of feature rows moved (``2 * len(pairs)``), also
            accumulated into :attr:`migrated_rows`.

        Raises:
            ValueError: if any node id appears in more than one pair.
        """
        if not pairs:
            return 0
        flat = [n for ab in pairs for n in ab]
        if len(set(flat)) != len(flat):
            raise ValueError("migration pairs must be disjoint")

        tier = np.asarray(self.tier_t).copy()
        slot = np.asarray(self.slot_t).copy()
        owner = np.asarray(self.owner_t).copy()
        stores = {TIER_HOT: self.hot, TIER_WARM: self.warm,
                  TIER_HOST: self.host, TIER_DISK: self.disk}

        # 1) read every feature row out of its current tier store
        feat = {n: np.asarray(stores[int(tier[n])][int(slot[n])]
                              )[:self.feat_dim]
                for n in flat}

        # 2) exchange table entries — all on copies (plan arrays too, so a
        #    failure anywhere before publish leaves the store untouched and
        #    plan never disagrees with the live tier tables)
        plan = self.plan
        p_tier, p_slot = plan.tier.copy(), plan.slot.copy()
        p_pod, p_dev = plan.pod_owner.copy(), plan.device_owner.copy()
        for a, b in pairs:
            for table in (tier, slot, owner, p_tier, p_slot, p_pod, p_dev):
                table[a], table[b] = table[b], table[a]

        # 3) write each row into its new home, copy-on-write per tier store
        writes: dict[int, tuple[list[int], list[np.ndarray]]] = {}
        for n in flat:
            rows, vals = writes.setdefault(int(tier[n]), ([], []))
            rows.append(int(slot[n]))
            vals.append(feat[n])
        new_stores = dict(stores)
        for t, (rows, vals) in writes.items():
            arr = stores[t]
            vals_np = np.stack(vals)
            if isinstance(arr, jnp.ndarray):
                new_stores[t] = arr.at[np.asarray(rows), :self.feat_dim].set(
                    jnp.asarray(vals_np, arr.dtype))
            else:
                arr = arr.copy()
                arr[np.asarray(rows)] = vals_np
                # bound the spill tier's RAM overlay under demotion churn:
                # fold it back into a fresh spill-file generation once it
                # outgrows an eighth of the tier
                if (isinstance(arr, DiskSpillTier)
                        and arr.overlay_rows > max(64, len(arr) // 8)):
                    arr = arr.compact()
                new_stores[t] = arr

        # 4) publish the new snapshot (tier tables + plan) atomically
        with self._mig_lock:
            self.hot = new_stores[TIER_HOT]
            self.warm = new_stores[TIER_WARM]
            self.host = new_stores[TIER_HOST]
            self.disk = new_stores[TIER_DISK]
            self.tier_t = jnp.asarray(tier, jnp.int32)
            self.slot_t = jnp.asarray(slot, jnp.int32)
            self.owner_t = jnp.asarray(owner, jnp.int32)
            plan.tier, plan.slot = p_tier, p_slot
            plan.pod_owner, plan.device_owner = p_pod, p_dev
            self.migrated_rows += 2 * len(pairs)
            cache = self.cache
        # invalidate ONLY the migrated rows from the device cache: a node
        # promoted into HBM must stop holding cache capacity. Correctness
        # never depends on this — rows travel with their nodes, so even a
        # lookup racing between publish and invalidate reads exact values.
        if cache is not None:
            cache.invalidate(flat)
        return 2 * len(pairs)


# ---------------------------------------------------------------------------
# Distributed store: shard_map one-sided reads over the mesh
# ---------------------------------------------------------------------------

# Canonical stats schema for ShardedFeatureStore dispatch accounting —
# mirrored by the `sharded-schema` table in docs/invariants.md and
# cross-checked against the class's stats declaration by quiverlint's
# schema-sync pass.
SHARDED_STATS_SCHEMA: tuple = (
    "exchanges", "exchanged_ids", "stage_hits", "stage_misses",
    "host_fetches", "cold_rows", "spill_reads")


def _new_sharded_stats() -> dict[str, int]:
    """Dispatch accounting for the sharded exchange (schema:
    ``SHARDED_STATS_SCHEMA``; benchmark signal:
    ``benchmarks/sharded_hierarchy.py``):

      exchanges        dedup ``all_to_all`` exchanges dispatched
      exchanged_ids    distinct (device, id) pairs moved through the
                       exchange — an id duplicated across hops costs one
                       entry however many positions repeat it
      stage_hits       cold id occurrences resolved from a per-shard
                       staging buffer inside the exchange
      stage_misses     cold id occurrences that fell through to the
                       host-side miss path
      host_fetches     host-side cold fetch round-trips actually issued
                       (a lookup whose cold ids are all staged issues 0)
      cold_rows        id occurrences those fetches resolved
      spill_reads      rows read from the per-shard DISK spill files
    """
    return {"exchanges": 0, "exchanged_ids": 0, "stage_hits": 0,
            "stage_misses": 0, "host_fetches": 0, "cold_rows": 0,
            "spill_reads": 0}


class ShardedFeatureStore:
    """Feature store laid out over a device mesh axis.

    hot  : (n_hot, d) replicated
    warm : (world * rows_per_dev, d) sharded on axis 0 over ``axis_name``

    Lookup runs under ``shard_map``, with two exchange strategies:

    ``"alltoall"`` (default) — the owner-sorted, capacity-bounded dedup
    exchange. Ids are deduplicated host-side across *all* hops of a
    sample, sorted by owner, padded to a pow2 per-(device, owner)
    capacity, and moved through two untiled ``jax.lax.all_to_all``
    collectives (requests out, rows back — the RDMA-read analogue: only
    distinct rows travel). Cold (HOST/DISK) ids resolve from per-shard
    staging buffers *inside* the same exchange when staged
    (:meth:`publish_stage`); only actual misses fall back to one
    host-side fetch (:meth:`read_cold_rows`) merged after the exchange.

    ``"allgather"`` (legacy) — allgather every wanted warm slot, owners
    answer, ``psum_scatter`` returns each requester's rows; every
    occurrence is exchanged and cold ids are resolved by a host
    post-pass.

    Both strategies are bit-identical to each other, to per-hop calls
    and to the single-host :class:`TieredFeatureStore` — rows are moved
    and selected, never operated on. Built via :meth:`from_tiered` the
    store keeps a reference to the source store for host fetches, and
    optionally per-shard :class:`DiskSpillTier` files (``spill_dir=``)
    so each shard owns its cold rows. Directly-constructed stores (no
    tiered source) keep the documented zeros behavior for cold ids.
    Dispatch counters land in :attr:`stats` (schema
    ``SHARDED_STATS_SCHEMA``), which the serving engine snapshots into
    ``ServeMetrics.summary()["store"]``.
    """

    def __init__(self, mesh: Mesh, axis_name: str, hot: jnp.ndarray,
                 warm: jnp.ndarray, tier_t: jnp.ndarray, slot_t: jnp.ndarray,
                 owner_t: jnp.ndarray, strategy: str = "alltoall"):
        self.mesh, self.axis = mesh, axis_name
        self.world = int(np.prod([mesh.shape[a] for a in
                                  (axis_name if isinstance(axis_name, tuple)
                                   else (axis_name,))]))
        if self.world and warm.shape[0] % self.world:
            raise ValueError(
                f"warm.shape[0] ({warm.shape[0]}) must be divisible by the "
                f"mesh world size ({self.world}) — a ragged warm buffer "
                f"would silently truncate the last shard")
        if strategy not in ("alltoall", "allgather"):
            raise ValueError(f"unknown exchange strategy {strategy!r} "
                             f"(want 'alltoall' or 'allgather')")
        self.rows_per_dev = warm.shape[0] // max(self.world, 1)
        self.strategy = strategy
        rep = NamedSharding(mesh, P())
        shard0 = NamedSharding(mesh, P(axis_name))
        self.hot = jax.device_put(hot, rep)
        self.warm = jax.device_put(warm, shard0)
        self.tier_t = jax.device_put(tier_t, rep)
        self.slot_t = jax.device_put(slot_t, rep)
        self.owner_t = jax.device_put(owner_t, rep)
        self.feat_dim = hot.shape[1]
        # host-side table mirrors (static — the sharded store never
        # migrates) so per-lookup prep costs no device round-trips
        self._tier_np = np.asarray(tier_t)
        self._slot_np = np.asarray(slot_t).astype(np.int64)
        self._owner_np = np.asarray(owner_t).astype(np.int64)
        self._has_cold = bool((self._tier_np >= TIER_HOST).any())
        self._tiered: Optional[TieredFeatureStore] = None
        self._spill: Optional[list] = None
        self._spill_slot: Optional[np.ndarray] = None
        self._spill_dtype = np.dtype(np.float32)
        self._stage = None
        self._stage_lock = threading.Lock()
        self.stats = _new_sharded_stats()
        self._stats_lock = threading.Lock()

    @staticmethod
    def from_tiered(store: TieredFeatureStore, mesh: Mesh, axis_name: str,
                    strategy: str = "alltoall", *,
                    spill_dir: Optional[str] = None) -> "ShardedFeatureStore":
        topo = store.plan.topology
        world = topo.num_pods * topo.devices_per_pod
        mesh_world = int(np.prod([mesh.shape[a] for a in
                                  (axis_name if isinstance(axis_name, tuple)
                                   else (axis_name,))]))
        assert world == mesh_world, (world, mesh_world)
        # pad warm shards to equal size and rebuild the slot table against
        # the padded bases
        rows = store.warm.shape[0]
        per = -(-rows // world)
        counts = np.diff(np.append(np.asarray(store.warm_base), rows))
        owner = np.asarray(store.owner_t)
        slot = np.asarray(store.slot_t).astype(np.int64)
        tier = np.asarray(store.tier_t)
        base = np.asarray(store.warm_base).astype(np.int64)
        warm_np = np.zeros((per * world, store.feat_dim),
                           np.asarray(store.warm).dtype)
        src = np.asarray(store.warm)[:, :store.feat_dim]
        new_slot = slot.copy()
        for w in range(world):
            c = int(counts[w])
            warm_np[w * per: w * per + c] = src[base[w]: base[w] + c]
            m = (tier == TIER_WARM) & (owner == w)
            new_slot[m] = slot[m] - base[w] + w * per
        ss = ShardedFeatureStore(
            mesh, axis_name, store.hot[:, :store.feat_dim],
            jnp.asarray(warm_np),
            store.tier_t, jnp.asarray(new_slot, dtype=jnp.int32),
            store.owner_t, strategy)
        ss._tiered = store    # cold-tier (HOST/DISK) host-fetch miss path
        if spill_dir is not None:
            ss._attach_spill(store, spill_dir)
        return ss

    def _attach_spill(self, store: TieredFeatureStore, spill_dir) -> None:
        """Build one per-shard :class:`DiskSpillTier` file per mesh device
        (shard ``w`` owns the DISK rows of ids with ``id % world == w``)
        plus the id → shard-local-row table the miss path reads through.
        Rows are copied at build time and stay exact under concurrent
        source-store migration: swaps move placements, never values."""
        world = max(self.world, 1)
        os.makedirs(spill_dir, exist_ok=True)
        n = self._tier_np.shape[0]
        spill_slot = np.full(n, -1, np.int32)
        tiers: list = []
        disk_ids = np.flatnonzero(self._tier_np == TIER_DISK)
        for w in range(world):
            ids_w = disk_ids[disk_ids % world == w]
            if ids_w.size == 0:
                tiers.append(None)
                continue
            rows = store.read_cold_rows(ids_w)
            path = os.path.join(spill_dir, f"shard{w:03d}.spill")
            tiers.append(DiskSpillTier.build(rows, path))
            spill_slot[ids_w] = np.arange(ids_w.size, dtype=np.int32)
            self._spill_dtype = rows.dtype
        self._spill = tiers
        self._spill_slot = spill_slot

    def read_cold_rows(self, ids: np.ndarray) -> np.ndarray:
        """Host-side exact reader for cold (HOST/DISK) rows — the dedup
        exchange's miss path and the staging source a
        :class:`~repro.core.prefetch.Prefetcher` reads through. DISK rows
        come from this store's per-shard spill files when built with
        ``from_tiered(..., spill_dir=...)`` (counted as ``spill_reads``);
        everything else — HOST rows, rows without a per-shard file, raced
        promotions — delegates to the source store's
        :meth:`TieredFeatureStore.read_cold_rows`. Plain numpy end to
        end, never an ``io_callback``: quiverlint's callback pass pins
        this as the only host-data route out of the sharded hot path.
        Without a tiered source (directly-constructed store) cold rows
        read as zeros."""
        ids = np.asarray(ids).reshape(-1)
        if self._spill is None or self._spill_slot is None:
            if self._tiered is None:
                return np.zeros((ids.shape[0], self.feat_dim),
                                self._spill_dtype)
            return self._tiered.read_cold_rows(ids)
        world = max(self.world, 1)
        safe = np.maximum(ids, 0)
        srow = self._spill_slot[safe]
        local = (ids >= 0) & (self._tier_np[safe] == TIER_DISK) & (srow >= 0)
        out = np.zeros((ids.shape[0], self.feat_dim), self._spill_dtype)
        if local.any():
            idx = np.flatnonzero(local)
            own = safe[idx] % world
            for w in np.unique(own):
                sel = idx[own == w]
                out[sel] = self._spill[int(w)][srow[sel]]
            with self._stats_lock:
                self.stats["spill_reads"] += int(local.sum())
        rest = (ids >= 0) & ~local
        if rest.any() and self._tiered is not None:
            out[rest] = self._tiered.read_cold_rows(ids[rest])
        return out

    def publish_stage(self, stage_slot, stage_rows) -> None:
        """Publish (``stage_slot, stage_rows``) or clear (``None, None``)
        the per-shard staging buffers. Accepts the global ``(N,)``
        id → staged-row layout the
        :class:`~repro.core.prefetch.Prefetcher` publishes (the
        :meth:`TieredFeatureStore.publish_stage` contract) and re-bins it
        per shard: cold id ``i`` goes to shard ``i % world``, every shard
        is padded to a shared pow2 row capacity, and the buffer is
        device_put sharded over the mesh axis — so the dedup exchange
        resolves staged cold ids with the exact same ``all_to_all`` that
        serves WARM rows, and one unmodified prefetcher feeds every
        shard."""
        if stage_slot is None or stage_rows is None:
            with self._stage_lock:
                self._stage = None
            return
        world = max(self.world, 1)
        stage_slot = np.asarray(stage_slot)
        rows_all = np.asarray(stage_rows)
        ids = np.flatnonzero(stage_slot >= 0)
        if ids.size == 0:
            with self._stage_lock:
                self._stage = None
            return
        rows = rows_all[stage_slot[ids]]
        owner = ids % world
        order = np.argsort(owner, kind="stable")
        ids_o, own_o = ids[order], owner[order]
        counts = np.bincount(own_o, minlength=world)
        cap = 1 << max(int(counts.max()) - 1, 0).bit_length()
        starts = np.zeros(world, np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        rank = np.arange(ids_o.size) - starts[own_o]
        local = np.full(stage_slot.shape[0], -1, np.int32)
        local[ids_o] = rank
        buf = np.zeros((world * cap, rows.shape[1]), rows.dtype)
        buf[own_o * cap + rank] = rows[order]
        buf_dev = jax.device_put(jnp.asarray(buf),
                                 NamedSharding(self.mesh, P(self.axis)))
        with self._stage_lock:
            self._stage = (local, buf_dev, int(cap))

    @property
    def tier_table_host(self) -> np.ndarray:
        """Host-side mirror of the per-node tier table. Static — the
        sharded store never migrates — so callers (the prefetcher's
        predict step, the cold post-pass gate) read it without a
        device→host transfer."""
        return self._tier_np

    def staged_rows(self) -> int:
        """Rows currently staged across all shards (0 with no stage)."""
        with self._stage_lock:
            stage = self._stage
        if stage is None:
            return 0
        return int((stage[0] >= 0).sum())

    def _snapshot_stage(self):
        with self._stage_lock:
            return self._stage

    def snapshot_stats(self) -> dict[str, int]:
        """Coherent copy of the dispatch counters."""
        with self._stats_lock:
            return dict(self.stats)

    def reset_stats(self) -> dict[str, int]:
        """Snapshot and zero the dispatch counters (benchmark windows)."""
        with self._stats_lock:
            out = dict(self.stats)
            for k in out:
                self.stats[k] = 0
        return out

    def _check_world_multiple(self, m: int, what: str) -> None:
        world = max(self.world, 1)
        if m == 0 or m % world:
            raise ValueError(
                f"{what} = {m} must be a non-zero multiple of the mesh "
                f"world size ({world}) so each device's shard is static — "
                f"pad with -1 (executor padding guarantees this)")

    def lookup(self, ids: jnp.ndarray) -> jnp.ndarray:
        """ids: (world * m,) global ids sharded over the axis (each device
        resolves m requests; ``-1`` pads to zeros). Returns
        (world * m, d) with the same sharding — bit-identical across
        strategies and to the single-host tiered store, HOST/DISK ids
        included.

        Raises:
            ValueError: when ``len(ids)`` is zero or not a multiple of
                the mesh world size (the per-device shard must be
                static)."""
        ids = jnp.asarray(ids).reshape(-1)
        self._check_world_multiple(int(ids.shape[0]), "len(ids)")
        if self.strategy == "allgather":
            return self._lookup_allgather(ids)
        return self._lookup_dedup(ids)

    def lookup_hops(self, hops) -> list[jnp.ndarray]:
        """Fused multi-hop variant of :meth:`lookup`: ONE exchange over
        the concatenated hop ids, rows scattered back per hop. Under the
        default ``"alltoall"`` strategy the ids are deduplicated across
        hops *before* the exchange, so a neighbor appearing in several
        hop frontiers crosses the interconnect once and its row fans back
        out through the inverse permutation — still bit-identical to
        per-hop calls.

        Args:
            hops: sequence of ``(M_k,)`` id vectors, each with ``-1``
                padding; every ``M_k`` must be a non-zero multiple of the
                mesh world size (executor padding guarantees this).

        Returns:
            List of ``(M_k, d)`` feature matrices, one per hop.

        Raises:
            ValueError: when any hop length is zero or not a multiple of
                the mesh world size — raised eagerly with the offending
                hop named, instead of failing opaquely inside
                ``shard_map``."""
        hops_j = [jnp.asarray(h).reshape(-1) for h in hops]
        if not hops_j:
            raise ValueError("lookup_hops needs at least one hop")
        sizes = [int(h.shape[0]) for h in hops_j]
        for k, s in enumerate(sizes):
            self._check_world_multiple(s, f"hop {k} length")
        ids = hops_j[0] if len(hops_j) == 1 else jnp.concatenate(hops_j)
        out = (self._lookup_allgather(ids) if self.strategy == "allgather"
               else self._lookup_dedup(ids))
        offs = np.concatenate([[0], np.cumsum(sizes)])
        return [out[int(offs[k]):int(offs[k + 1])]
                for k in range(len(sizes))]

    def _lookup_allgather(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Legacy exchange: allgather every wanted warm slot, owners
        answer, ``psum_scatter`` returns each requester's rows; cold ids
        are resolved by a host-side post-pass. Kept as the baseline the
        ``sharded_hierarchy`` benchmark measures the dedup exchange
        against."""
        axis = self.axis
        per = self.rows_per_dev

        def allgather_body(hot, warm, tier_t, slot_t, owner_t, ids_l):
            my = jax.lax.axis_index(axis)
            safe = jnp.maximum(ids_l, 0)
            tier = tier_t[safe]
            slot = slot_t[safe]
            out = jnp.zeros((ids_l.shape[0], self.feat_dim), hot.dtype)
            out = jnp.where((tier == TIER_HOT)[:, None],
                            hot[jnp.minimum(slot, hot.shape[0] - 1)], out)
            is_warm = tier == TIER_WARM
            local = is_warm & (owner_t[safe] == my)
            lrow = jnp.clip(slot - my * per, 0, per - 1)
            out = jnp.where(local[:, None], warm[lrow], out)
            remote = is_warm & ~local
            # one-sided read: every device publishes its wanted global warm
            # rows; owners answer; reduce_scatter returns each requester's rows
            want_slot = jnp.where(remote, slot, -1)
            all_want = jax.lax.all_gather(want_slot, axis)      # (W, m)
            owned = (all_want >= my * per) & (all_want < (my + 1) * per)
            rows = warm[jnp.clip(all_want - my * per, 0, per - 1)]
            rows = jnp.where(owned[..., None], rows, 0.0)        # (W, m, d)
            answered = jax.lax.psum_scatter(rows, axis, scatter_dimension=0,
                                            tiled=False)         # (m, d)
            answered = answered.reshape(ids_l.shape[0], self.feat_dim)
            out = jnp.where(remote[:, None], answered, out)
            return jnp.where((ids_l >= 0)[:, None], out, 0.0)

        fn = jax.shard_map(
            allgather_body, mesh=self.mesh,
            in_specs=(P(), P(axis), P(), P(), P(), P(axis)),
            out_specs=P(axis))
        out = fn(self.hot, self.warm, self.tier_t, self.slot_t, self.owner_t,
                 ids)
        # cold (HOST/DISK) post-pass. The static tier mirror gates the
        # device→host transfer of the id vector: a store with no cold
        # tiers at all never pays it.
        if self._tiered is None or not self._has_cold:
            return out
        ids_np = np.asarray(ids).reshape(-1)
        cold = (ids_np >= 0) & (self._tier_np[np.maximum(ids_np, 0)]
                                >= TIER_HOST)
        if not cold.any():
            return out
        rows = np.zeros((ids_np.shape[0], self.feat_dim),
                        dtype=np.dtype(out.dtype))
        rows[cold] = self._tiered.read_cold_rows(ids_np[cold])
        with self._stats_lock:
            self.stats["host_fetches"] += 1
            self.stats["cold_rows"] += int(cold.sum())
        shard0 = NamedSharding(self.mesh, P(self.axis))
        rows_j = jax.device_put(jnp.asarray(rows, out.dtype), shard0)
        mask = jax.device_put(jnp.asarray(cold), shard0)
        return jnp.where(mask[:, None], rows_j, out)

    def _lookup_dedup(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Owner-sorted, capacity-bounded dedup exchange (strategy
        ``"alltoall"``).

        Host-side prep: each device's slice of the request vector is
        deduplicated (across every hop of a fused sample), classified per
        tier, and the distinct WARM/staged-cold ids are sorted by owner
        into a ``(world, world, cap)`` request tensor — ``cap`` is the
        pow2 ceiling of the max per-(device, owner) count, so recompiles
        stay bounded while shapes stay static. Inside ``shard_map`` the
        requests move to their owners with one untiled ``all_to_all``,
        owners answer with a single local gather from
        ``concat(warm_shard, stage_shard)``, a second ``all_to_all``
        carries the rows back, and an inverse permutation scatters each
        device's distinct rows to its request positions. HOT rows gather
        from the replicated buffer; cold ids without a staged row fall
        back to one host-side :meth:`read_cold_rows` fetch merged after
        the exchange — the miss path, counted only when actually issued.
        Rows are moved and selected, never summed, which is what keeps
        every path bit-identical."""
        world = max(self.world, 1)
        per = self.rows_per_dev
        d = self.feat_dim
        ids_np = np.asarray(ids).reshape(-1).astype(np.int64)
        m = ids_np.shape[0]
        m_dev = m // world
        stage = self._snapshot_stage()
        stage_local, stage_buf, _stage_cap = (
            stage if stage is not None else (None, None, 1))

        safe = np.maximum(ids_np, 0)
        tier = self._tier_np[safe]
        valid = ids_np >= 0
        is_hot = valid & (tier == TIER_HOT)
        is_warm = valid & (tier == TIER_WARM)
        is_cold = valid & (tier >= TIER_HOST)
        staged = (is_cold & (stage_local[safe] >= 0)
                  if stage_local is not None
                  else np.zeros(m, dtype=bool))
        exch = is_warm | staged
        miss = is_cold & ~staged

        # owner + owner-local row into concat(warm_shard, stage_shard);
        # values at non-exchange positions are never read
        owner = np.where(is_warm, self._owner_np[safe], safe % world)
        lrow = np.where(is_warm, self._slot_np[safe] - owner * per,
                        per + (stage_local[safe]
                               if stage_local is not None else 0))
        # per-device cross-hop dedup: device i requests each distinct id
        # in its slice once, whatever the hop multiplicity
        dev = np.repeat(np.arange(world), m_dev)
        eidx = np.flatnonzero(exch)
        n = self._tier_np.shape[0]
        pair = dev[eidx] * (n + 1) + ids_np[eidx]
        upair, urep, uinv = np.unique(pair, return_index=True,
                                      return_inverse=True)
        rep = eidx[urep]
        u_dev, u_own, u_row = dev[rep], owner[rep], lrow[rep]
        # owner-sort within each device (address-sorted requests);
        # cap = pow2 ceiling of the max per-(device, owner) count
        order = np.lexsort((u_row, u_own, u_dev))
        sd, so, sr = u_dev[order], u_own[order], u_row[order]
        grp = sd * world + so
        first = np.ones(grp.shape[0], dtype=bool)
        first[1:] = grp[1:] != grp[:-1]
        gstart = np.flatnonzero(first)
        glen = np.diff(np.append(gstart, grp.shape[0]))
        rank = np.arange(grp.shape[0]) - np.repeat(gstart, glen)
        cmax = int(glen.max()) if glen.size else 0
        cap = 1 << max(cmax - 1, 0).bit_length()
        req = np.full((world * world, cap), -1, np.int32)
        req[sd * world + so, rank] = sr
        # per-unique index into its requesting device's flat (world*cap)
        # answer buffer, then fanned out to every request position
        sel_u = np.zeros(upair.shape[0], np.int64)
        sel_u[order] = so * cap + rank
        sel = np.full(m, -1, np.int64)
        sel[eidx] = sel_u[uinv]
        hslot = np.where(is_hot, self._slot_np[safe], -1)

        with self._stats_lock:
            self.stats["exchanges"] += 1
            self.stats["exchanged_ids"] += int(upair.shape[0])
            self.stats["stage_hits"] += int(staged.sum())
            self.stats["stage_misses"] += int(miss.sum())

        axis = self.axis
        stage_g = (stage_buf if stage_buf is not None
                   else jnp.zeros((world, d), self.warm.dtype))

        def exchange_body(hot, warm_l, stage_l, req_l, sel_l, hslot_l):
            buf = jnp.concatenate([warm_l, stage_l], axis=0)
            incoming = jax.lax.all_to_all(req_l, axis, 0, 0)    # (W, cap)
            ans = buf[jnp.clip(incoming, 0, buf.shape[0] - 1)]  # (W, cap, d)
            back = jax.lax.all_to_all(ans, axis, 0, 0)
            flat = back.reshape(world * cap, d)
            out = jnp.zeros((sel_l.shape[0], d), hot.dtype)
            out = jnp.where((hslot_l >= 0)[:, None],
                            hot[jnp.clip(hslot_l, 0, hot.shape[0] - 1)], out)
            return jnp.where((sel_l >= 0)[:, None],
                             flat[jnp.clip(sel_l, 0, flat.shape[0] - 1)],
                             out)

        fn = jax.shard_map(
            exchange_body, mesh=self.mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis))
        out = fn(self.hot, self.warm, stage_g, jnp.asarray(req),
                 jnp.asarray(sel, dtype=jnp.int32),
                 jnp.asarray(hslot, dtype=jnp.int32))
        if not miss.any():
            return out
        if self._tiered is None and self._spill is None:
            return out    # no cold source: documented zeros behavior
        miss_ids, minv = np.unique(ids_np[miss], return_inverse=True)
        rows = np.zeros((m, d), dtype=np.dtype(out.dtype))
        rows[miss] = self.read_cold_rows(miss_ids)[minv]
        with self._stats_lock:
            self.stats["host_fetches"] += 1
            self.stats["cold_rows"] += int(miss.sum())
        shard0 = NamedSharding(self.mesh, P(self.axis))
        rows_j = jax.device_put(jnp.asarray(rows, out.dtype), shard0)
        mask = jax.device_put(jnp.asarray(miss), shard0)
        return jnp.where(mask[:, None], rows_j, out)
