"""Pluggable serving executors (paper §4.2–§4.3, generalized).

Quiver's serving contribution is *workload-aware routing between executors*:
the paper ships exactly two (host sampler vs device sampler). This module
turns "executor" into a first-class, pluggable unit so the router can choose
among N of them:

  ``HostExecutor``     exact dynamic-shape sampling on the host (CPU path).
  ``DeviceExecutor``   padded static-shape sampling on one accelerator
                       (GPU path); oversized batches are *chunked*, never
                       silently truncated.
  ``ShardedExecutor``  the distributed path: mesh-local sampling under
                       ``shard_map`` plus one-sided sharded feature reads
                       through ``ShardedFeatureStore.lookup``.

Every executor owns ``capacity`` worker lanes (the paper's "multiplexed
pipelines in a processor", §4.3(1)) and exposes

  ``cost(seeds)``   accumulated PSGS of the batch — O(1) per seed,
  ``submit(seeds)`` → ``concurrent.futures.Future`` of the model output,
  ``capacity``      number of batches it can process concurrently.

This module must stay importable without ``repro.core`` (the core package
shims onto it), so it depends only on ``repro.graph``, ``repro.tracing`` and
numpy/jax.
"""
from __future__ import annotations

import threading
import warnings
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.graph.sampler import (_sample_one_hop, device_sample,
                                 host_sample_dense)


def pad_to_bucket(arr: np.ndarray, *, min_size: int = 16,
                  fill: int = -1) -> np.ndarray:
    """Pad a dynamic-size host array up to the next power-of-two bucket so
    jit re-compilation is bounded to O(log max_size) shapes."""
    n = max(int(arr.shape[0]), 1)
    size = max(min_size, 1 << (n - 1).bit_length())
    out = np.full((size,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[:arr.shape[0]] = arr  # arr may be empty: pad-only bucket
    return out


def _accumulated_psgs(psgs_table: np.ndarray, seeds: np.ndarray) -> float:
    """Accumulated PSGS of a batch (paper §4.2.2). Local copy of
    ``repro.core.psgs.batch_psgs`` so this package stays core-free."""
    seeds = np.asarray(seeds)
    valid = seeds >= 0
    return float(psgs_table[seeds[valid]].sum())


@runtime_checkable
class Executor(Protocol):
    """What the router and engine require of an executor.

    Attributes:
        name: registry key used by the router and the engine.
        kind: ``"host"`` | ``"device"`` — selects which latency statistic
            a routing policy judges this executor by (Fig. 6(b) roles).
        capacity: number of concurrent worker lanes (batches in flight).
    """

    name: str
    kind: str           # "host" | "device" | ... (policy stat selection)
    capacity: int

    def cost(self, seeds: np.ndarray) -> float:
        """Routing signal for a batch.

        Args:
            seeds: ``(B,)`` seed node ids, ``-1`` entries ignored.

        Returns:
            Accumulated PSGS of the batch (batch size when the executor has
            no PSGS table).
        """
        ...

    def submit(self, seeds: np.ndarray) -> Future:
        """Enqueue a batch on one of the executor's worker lanes.

        Args:
            seeds: ``(B,)`` seed node ids.

        Returns:
            A future resolving to the ``(B, d_out)`` model output (one row
            per seed — padding is an internal concern).
        """
        ...


class BaseExecutor:
    """Shared machinery: worker lanes, PSGS costing, inflight accounting.

    Subclasses implement ``process(seeds) -> jnp.ndarray`` returning one
    output row per seed (padding is an internal concern — callers never see
    truncated or zero-filled extra rows).
    """

    kind = "device"

    def __init__(self, name: str, *, capacity: int = 1,
                 psgs_table: Optional[np.ndarray] = None,
                 rng_seed: int = 0, fused: bool = True,
                 fuse_aggregate: bool = False):
        self.name = name
        self.capacity = int(capacity)
        self.psgs_table = psgs_table
        # fused feature collection: one cross-hop dedup + one gather per
        # tier class (store.lookup_hops) instead of per-hop lookups. Output
        # is bit-identical; the flag exists for equivalence testing and for
        # stores that only implement lookup().
        self.fused = bool(fused)
        # fused gather→aggregate: the store also reduces the innermost hop
        # into per-parent sums (store.lookup_aggregate), so the dense
        # deepest-hop tensor never materializes. Requires an ``infer_fn``
        # accepting ``deep_agg=``; the flag is opt-in for that reason.
        self.fuse_aggregate = bool(fuse_aggregate)
        self._pool = ThreadPoolExecutor(max_workers=self.capacity,
                                        thread_name_prefix=f"exec-{name}")
        self._lock = threading.Lock()
        self._inflight = 0
        self._key = jax.random.key(rng_seed)
        self._seed_rng = np.random.default_rng(rng_seed)

    # -- cost model signal ---------------------------------------------------
    def cost(self, seeds: np.ndarray) -> float:
        """Routing signal: accumulated PSGS (or batch size if no table)."""
        seeds = np.asarray(seeds)
        if self.psgs_table is None:
            return float((seeds >= 0).sum())
        return _accumulated_psgs(self.psgs_table, seeds)

    # -- rng (thread-safe draws for concurrent lanes) ------------------------
    def _next_key(self) -> jax.Array:
        with self._lock:
            self._key, sub = jax.random.split(self._key)
        return sub

    def _child_rng(self) -> np.random.Generator:
        with self._lock:
            seed = int(self._seed_rng.integers(0, 2**63))
        return np.random.default_rng(seed)

    # -- execution -----------------------------------------------------------
    @property
    def inflight(self) -> int:
        """Batches currently submitted and not yet completed (the router's
        load-aware signal)."""
        with self._lock:
            return self._inflight

    def process(self, seeds: np.ndarray) -> jnp.ndarray:
        """Subclass hook: sample + collect features + infer for one batch.

        Args:
            seeds: ``(B,)`` seed node ids (``-1`` padding allowed).

        Returns:
            ``(B, d_out)`` model output, one row per input seed.

        Raises:
            NotImplementedError: on the base class.
        """
        raise NotImplementedError

    def _collect(self, store, hops):
        """Feature collection for a layered sample. Returns
        ``(hop_feats, deep_agg)``: the fused gather→aggregate fast path
        (``store.lookup_aggregate``) when ``fuse_aggregate`` is enabled and
        the store supports it — ``hop_feats`` then omits the innermost hop
        and ``deep_agg`` carries its pre-reduced per-parent sums — else the
        fused single-dispatch path (``store.lookup_hops``) or the legacy
        per-hop loop, both with ``deep_agg=None``."""
        if (self.fuse_aggregate and len(hops) > 1
                and hasattr(store, "lookup_aggregate")):
            return store.lookup_aggregate(hops)
        if self.fused and hasattr(store, "lookup_hops"):
            return store.lookup_hops(hops), None
        return [store.lookup(h) for h in hops], None

    def collect_mode(self, store) -> str:
        """The feature-collection path :meth:`_collect` takes for ``store``
        under the current flags on a multi-hop sample:
        ``"fuse_aggregate"`` (gather→aggregate fusion), ``"fused"``
        (single cross-hop ``lookup_hops`` dispatch) or ``"per_hop"`` (the
        legacy loop). The engine surfaces this per store in
        ``ServeMetrics.summary()["store"]`` so a silently-downgraded flag
        — e.g. ``fuse_aggregate=True`` against a store without
        ``lookup_aggregate`` — is visible in telemetry, not just in a
        construction-time warning. See the support matrix in
        ``docs/architecture.md``."""
        if self.fuse_aggregate and hasattr(store, "lookup_aggregate"):
            return "fuse_aggregate"
        if self.fused and hasattr(store, "lookup_hops"):
            return "fused"
        return "per_hop"

    def supports(self, seeds: np.ndarray) -> bool:
        """Eligibility for a batch — routers skip executors returning False
        (e.g. the sharded executor cannot serve cold-tier seeds exactly)."""
        return True

    def stores(self) -> list:
        """The feature store(s) this executor reads (shared across the
        models of a registry) — the engine snapshots their dispatch stats
        into ``ServeMetrics.store_stats`` at the end of a run."""
        return [s for s in (getattr(self, "store", None),
                            getattr(self, "sstore", None)) if s is not None]

    def run(self, seeds: np.ndarray) -> jnp.ndarray:
        """Process one batch and wait for its output: the body of a worker
        lane, and the synchronous path of calibration and warm-up."""
        with tracing.span("executor.run", kind=self.kind):
            out = self.process(np.asarray(seeds))
            with tracing.span("executor.sync"):
                jax.block_until_ready(out)
        return out

    def submit(self, seeds: np.ndarray) -> Future:
        """Enqueue a batch on a worker lane (see :class:`Executor.submit`);
        resolves to the ``(B, d_out)`` output of :meth:`process`."""
        with self._lock:
            self._inflight += 1
        fut = self._pool.submit(tracing.in_lane(self.run), seeds)
        fut.add_done_callback(self._one_done)
        return fut

    def _one_done(self, _fut: Future) -> None:
        with self._lock:
            self._inflight -= 1

    def warmup(self, seeds: np.ndarray, *, rounds: int = 2) -> None:
        """Run ``rounds`` synchronous passes so jit compilation happens
        outside any measured window."""
        for _ in range(rounds):
            self.run(seeds)

    def close(self) -> None:
        """Shut down the worker-lane pool (blocks until lanes drain)."""
        self._pool.shutdown(wait=True)


class HostExecutor(BaseExecutor):
    """Exact host sampling (the 'CPU path') in the dense fan-out layout;
    seeds bucket-padded so jit shapes stay O(log max_batch)."""

    kind = "host"

    def __init__(self, graph, store, fanouts: Sequence[int],
                 infer_fn: Callable, *, capacity: int = 1,
                 psgs_table: Optional[np.ndarray] = None, rng_seed: int = 0,
                 fused: bool = True, fuse_aggregate: bool = False,
                 name: str = "host"):
        super().__init__(name, capacity=capacity, psgs_table=psgs_table,
                         rng_seed=rng_seed, fused=fused,
                         fuse_aggregate=fuse_aggregate)
        self.graph = graph
        self.store = store
        self.fanouts = tuple(fanouts)
        self.infer_fn = infer_fn

    def process(self, seeds: np.ndarray) -> jnp.ndarray:
        """Exact host sampling → (fused) feature collection → inference;
        returns one output row per seed."""
        n = int(seeds.shape[0])
        seeds_p = pad_to_bucket(np.asarray(seeds).astype(np.int32))
        with tracing.span("executor.sample"):
            hops_np = host_sample_dense(self._child_rng(), self.graph,
                                        seeds_p, self.fanouts)
            hops = [jnp.asarray(h) for h in hops_np]
        with tracing.span("executor.collect"):
            hop_feats, deep_agg = self._collect(self.store, hops)
        with tracing.span("executor.infer"):
            if deep_agg is not None:
                return self.infer_fn(hop_feats, hops, deep_agg=deep_agg)[:n]
            return self.infer_fn(hop_feats, hops)[:n]


class DeviceExecutor(BaseExecutor):
    """Fully padded on-device pipeline (the 'GPU path'): one static shape
    (``max_batch``), jitted end to end. Batches larger than ``max_batch``
    are processed in ``max_batch``-sized chunks and re-concatenated — no
    seed is ever dropped (the old ``_device_path`` silently truncated)."""

    kind = "device"

    def __init__(self, graph_dev: tuple[jnp.ndarray, jnp.ndarray], store,
                 fanouts: Sequence[int], infer_fn: Callable, *,
                 max_batch: int = 128, capacity: int = 1,
                 psgs_table: Optional[np.ndarray] = None, rng_seed: int = 0,
                 fused: bool = True, fuse_aggregate: bool = False,
                 name: str = "device"):
        super().__init__(name, capacity=capacity, psgs_table=psgs_table,
                         rng_seed=rng_seed, fused=fused,
                         fuse_aggregate=fuse_aggregate)
        self.graph_dev = graph_dev
        self.store = store
        self.fanouts = tuple(fanouts)
        self.infer_fn = infer_fn
        self.max_batch = int(max_batch)

    def process(self, seeds: np.ndarray) -> jnp.ndarray:
        """Padded device sampling → (fused) feature collection → inference,
        chunked at ``max_batch``; returns one output row per seed."""
        seeds = np.asarray(seeds)
        n = int(seeds.shape[0])
        outs = []
        for lo in range(0, max(n, 1), self.max_batch):
            chunk = seeds[lo:lo + self.max_batch]
            seeds_p = np.full((self.max_batch,), -1, np.int32)
            seeds_p[:chunk.shape[0]] = chunk
            with tracing.span("executor.sample"):
                hops = device_sample(self._next_key(), *self.graph_dev,
                                     jnp.asarray(seeds_p), self.fanouts)
            with tracing.span("executor.collect"):
                hop_feats, deep_agg = self._collect(self.store, hops)
            with tracing.span("executor.infer"):
                out = (self.infer_fn(hop_feats, hops, deep_agg=deep_agg)
                       if deep_agg is not None
                       else self.infer_fn(hop_feats, hops))
            outs.append(out[:chunk.shape[0]])
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


class ShardedExecutor(BaseExecutor):
    """Distributed serving path over a device mesh axis.

    Sampling runs mesh-local under ``shard_map`` (each device samples its
    contiguous slice of the seed vector against the replicated CSR
    topology); features come from the sharded store's fused
    ``lookup_hops`` — by default the owner-sorted dedup ``all_to_all``
    exchange of paper §5.3. A store built via
    ``ShardedFeatureStore.from_tiered`` resolves HOST/DISK rows exactly
    (per-shard staged rows inside the exchange, host fetch on a miss);
    only a directly-constructed store keeps the legacy zeros behavior
    for cold ids — pass ``tier_table`` (the placement's per-node tier
    array) there so :meth:`supports` declares cold-seed batches
    ineligible and the router keeps them on the host executor.

    Feature-collection support matrix: the sharded store serves whole
    rows only, so ``fuse_aggregate=True`` (the gather→aggregate fusion of
    ``TieredFeatureStore.lookup_aggregate``) cannot apply here — it is
    accepted for construction-site symmetry with the other executors but
    warns once and falls back to the fused ``lookup_hops`` path; the
    active mode is surfaced per store as ``collect_mode`` in
    ``ServeMetrics.summary()["store"]`` (full matrix:
    ``docs/architecture.md``).

    ``max_batch`` is rounded up to a multiple of the mesh world size so the
    per-device shard is static.
    """

    kind = "device"
    _warned_fuse_aggregate = False

    def __init__(self, mesh, axis_name: str,
                 graph_dev: tuple[jnp.ndarray, jnp.ndarray],
                 sharded_store, fanouts: Sequence[int], infer_fn: Callable, *,
                 max_batch: int = 128, capacity: int = 1,
                 psgs_table: Optional[np.ndarray] = None,
                 tier_table: Optional[np.ndarray] = None, rng_seed: int = 0,
                 fused: bool = True, fuse_aggregate: bool = False,
                 name: str = "sharded"):
        super().__init__(name, capacity=capacity, psgs_table=psgs_table,
                         rng_seed=rng_seed, fused=fused,
                         fuse_aggregate=fuse_aggregate)
        if fuse_aggregate and not hasattr(sharded_store, "lookup_aggregate"):
            self._warn_fuse_aggregate_downgrade()
        self.tier_table = tier_table
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.mesh = mesh
        self.axis = axis_name
        self.sstore = sharded_store
        self.world = int(sharded_store.world)
        self.max_batch = -(-int(max_batch) // self.world) * self.world
        self.fanouts = tuple(fanouts)
        self.infer_fn = infer_fn
        rep = NamedSharding(mesh, P())
        self.graph_dev = tuple(jax.device_put(a, rep) for a in graph_dev)

        fanouts_t = self.fanouts
        axis = axis_name

        def sample_body(indptr, indices, seeds_l, key):
            # per-device stream: fold the lane key with the device index
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
            hops = [seeds_l]
            frontier = seeds_l
            for fan in fanouts_t:
                key, sub = jax.random.split(key)
                frontier = _sample_one_hop(sub, indptr, indices, frontier,
                                           fan)
                hops.append(frontier)
            return tuple(hops)

        self._sample = jax.jit(jax.shard_map(
            sample_body, mesh=mesh,
            in_specs=(P(), P(), P(axis), P()), out_specs=P(axis)))

    @classmethod
    def _warn_fuse_aggregate_downgrade(cls) -> None:
        if cls._warned_fuse_aggregate:
            return
        cls._warned_fuse_aggregate = True
        warnings.warn(
            "ShardedExecutor: fuse_aggregate=True has no effect — the "
            "sharded store serves whole rows only (no lookup_aggregate); "
            "falling back to the fused lookup_hops path. The active mode "
            "is reported as collect_mode in "
            "ServeMetrics.summary()['store']; see the support matrix in "
            "docs/architecture.md.", RuntimeWarning, stacklevel=3)

    def supports(self, seeds: np.ndarray) -> bool:
        """Eligible only when every valid seed lives on an HBM tier.
        Stores built via ``from_tiered`` resolve cold rows exactly, so
        they leave ``tier_table`` unset and accept every batch; a
        directly-constructed store (cold ids read as zeros) passes the
        placement's tier array here so the router keeps cold-seed batches
        on the host executor. Always ``True`` without a ``tier_table``."""
        if self.tier_table is None:
            return True
        seeds = np.asarray(seeds)
        seeds = seeds[seeds >= 0]
        # tiers 0/1 are the HBM (hot/warm) tiers the sharded store serves
        return bool((self.tier_table[seeds] <= 1).all())

    def process(self, seeds: np.ndarray) -> jnp.ndarray:
        """Mesh-local shard_map sampling → (fused) sharded feature reads →
        inference, chunked at the mesh-padded ``max_batch``; returns one
        output row per seed."""
        seeds = np.asarray(seeds)
        n = int(seeds.shape[0])
        outs = []
        for lo in range(0, max(n, 1), self.max_batch):
            chunk = seeds[lo:lo + self.max_batch]
            seeds_p = np.full((self.max_batch,), -1, np.int32)
            seeds_p[:chunk.shape[0]] = chunk
            with tracing.span("executor.sample"):
                hops = list(self._sample(*self.graph_dev,
                                         jnp.asarray(seeds_p),
                                         self._next_key()))
            # ShardedFeatureStore has no lookup_aggregate — _collect falls
            # back to the fused whole-row path there, deep_agg stays None
            with tracing.span("executor.collect"):
                hop_feats, deep_agg = self._collect(self.sstore, hops)
            with tracing.span("executor.infer"):
                out = (self.infer_fn(hop_feats, hops, deep_agg=deep_agg)
                       if deep_agg is not None
                       else self.infer_fn(hop_feats, hops))
            outs.append(out[:chunk.shape[0]])
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
