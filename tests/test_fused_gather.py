"""Fused feature-collection path (PR 3): lookup_hops bit-identical to the
per-hop path (incl. under concurrent live migration), the Pallas
tiered_gather dispatch it rides on, executor-level fused/legacy output
equivalence, the MicroBatcher coalescing stage, and dispatch accounting."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DynamicBatcher, MicroBatcher, Request,
                        TieredFeatureStore, TopologySpec, compute_fap,
                        compute_psgs, migration_pairs, quiver_placement)
from repro.graph import power_law_graph
from repro.kernels.tiered_gather.ops import tiered_gather
from repro.kernels.tiered_gather.ref import tiered_gather_ref
from repro.models.gnn_basic import sage_init, sage_layered
from repro.serving import (DeviceExecutor, HostExecutor, ServingEngine,
                           StaticScheduler)


# ---------------------------------------------------------------------------
# Fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def stack():
    n, d, fan = 900, 12, (4, 3)
    g = power_law_graph(n, 6.0, seed=0)
    feats = np.random.default_rng(1).normal(size=(n, d)).astype(np.float32)
    fap = compute_fap(g, fan)
    topo = TopologySpec(num_pods=1, devices_per_pod=1, rows_per_device=220,
                        rows_host=330, hot_replicate_fraction=0.3)
    return g, fan, feats, fap, topo


def _fresh_store(stack):
    g, fan, feats, fap, topo = stack
    return TieredFeatureStore.build(feats, quiver_placement(fap, topo))


def _rand_hops(n, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(-1, n, size=s).astype(np.int32) for s in sizes]


# ---------------------------------------------------------------------------
# lookup_hops: bit-identical to the per-hop path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes", [(16,), (16, 64), (16, 64, 192), (1, 1)])
def test_lookup_hops_bit_identical(stack, sizes):
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    hops = _rand_hops(g.num_nodes, sizes, seed=sum(sizes))
    per_hop = [np.asarray(store.lookup(jnp.asarray(h))) for h in hops]
    fused = store.lookup_hops(hops)
    assert len(fused) == len(hops)
    for a, b in zip(per_hop, fused):
        assert np.array_equal(a, np.asarray(b))  # bit-identical, not close


def test_lookup_hops_pallas_interpret_bit_identical(stack):
    """The fused path with the Pallas kernel forced on (interpret mode off
    TPU) must still match the per-hop path bit for bit."""
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    hops = _rand_hops(g.num_nodes, (16, 48), seed=9)
    per_hop = [np.asarray(store.lookup(jnp.asarray(h))) for h in hops]
    fused = store.lookup_hops(hops, use_pallas=True)
    for a, b in zip(per_hop, fused):
        assert np.array_equal(a, np.asarray(b))


def test_lookup_hops_all_padding_and_exclude_host(stack):
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    hops = [np.full(8, -1, np.int32), np.full(24, -1, np.int32)]
    for out in store.lookup_hops(hops):
        assert not np.asarray(out).any()            # padding rows are zeros
    with pytest.raises(ValueError, match="non-empty"):
        store.lookup_hops([])
    # include_host=False zeroes the slow tiers in both paths identically
    ids = _rand_hops(g.num_nodes, (64,), seed=3)[0]
    a = np.asarray(store.lookup(jnp.asarray(ids), include_host=False))
    [b] = store.lookup_hops([ids], include_host=False)
    assert np.array_equal(a, np.asarray(b))


def test_lookup_hops_bit_identical_under_concurrent_migration(stack):
    """Reuse of the snapshot-consistency harness (tests/test_adaptive.py):
    a reader doing *fused* lookups while the main thread migrates rows must
    only ever see exact features — the fused path takes ONE snapshot for
    the entire multi-hop gather."""
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    rng = np.random.default_rng(7)
    hops = [rng.integers(0, g.num_nodes, 16).astype(np.int32),
            rng.integers(0, g.num_nodes, 48).astype(np.int32)]
    expected = [feats[h] for h in hops]
    stop = threading.Event()
    errors: list[str] = []

    def reader():
        while not stop.is_set():
            got = store.lookup_hops(hops)
            for e, o in zip(expected, got):
                if not np.allclose(np.asarray(o), e, rtol=1e-5):
                    errors.append("torn fused lookup during migration")
                    return

    t = threading.Thread(target=reader)
    t.start()
    try:
        drifted = fap.copy()
        drifted[np.argsort(fap)[:80]] += fap.max() * 3
        tgt = quiver_placement(drifted, topo)
        for _ in range(10):
            pairs = migration_pairs(store.plan.tier, tgt.tier, drifted,
                                    budget=20)
            if not pairs:
                break
            store.swap_assignments(pairs)
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors
    for e, o in zip(expected, store.lookup_hops(hops)):
        np.testing.assert_allclose(np.asarray(o), e, rtol=1e-6)


def test_dispatch_stats_reduction(stack):
    """The structural claim: per-hop pays 2 gathers + 1 host fetch per hop,
    fused pays 1 + 1 for the whole sample."""
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    hops = _rand_hops(g.num_nodes, (16, 64, 192), seed=2)
    store.reset_stats()
    [store.lookup(jnp.asarray(h)) for h in hops]
    old = store.reset_stats()
    assert old["device_gathers"] == 2 * len(hops)
    assert old["host_fetches"] == len(hops)
    store.lookup_hops(hops)
    new = store.reset_stats()
    assert new["device_gathers"] == 1 and new["host_fetches"] == 1
    assert new["fused_calls"] == 1 and new["lookup_calls"] == 0


# ---------------------------------------------------------------------------
# tiered_gather dispatch entry (ops): Pallas-interpret vs ref on CPU
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,h,w,d", [(33, 16, 40, 32), (128, 8, 8, 16)])
def test_tiered_gather_ops_pallas_vs_ref_cpu(m, h, w, d):
    rng = np.random.default_rng(m)
    hot = jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
    warm = jnp.asarray(rng.normal(size=(w, d)), jnp.float32)
    tier = rng.integers(0, 4, size=m).astype(np.int32)
    slot = np.where(tier == 0, rng.integers(0, h, m),
                    rng.integers(0, w, m)).astype(np.int32)
    via_pallas = tiered_gather(jnp.asarray(tier), jnp.asarray(slot), hot,
                               warm, use_pallas=True)   # interpret off-TPU
    via_ref = tiered_gather_ref(jnp.asarray(tier), jnp.asarray(slot), hot,
                                warm)
    assert np.array_equal(np.asarray(via_pallas), np.asarray(via_ref))


# ---------------------------------------------------------------------------
# Executor-level: fused vs legacy output equivalence
# ---------------------------------------------------------------------------
def _infer(stack):
    g, fan, feats, fap, topo = stack
    params = sage_init(jax.random.key(0), [feats.shape[1], 16, 16])

    @jax.jit
    def infer_fn(hop_feats, hop_ids):
        masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
        return sage_layered(params, hop_feats, fan, hop_masks=masks)

    return infer_fn


def test_host_executor_fused_matches_legacy(stack):
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    infer_fn = _infer(stack)
    seeds = np.arange(12)
    outs = {}
    for fused in (False, True):
        ex = HostExecutor(g, store, fan, infer_fn, rng_seed=5, fused=fused)
        outs[fused] = np.asarray(ex.run(seeds))
        ex.close()
    assert np.array_equal(outs[False], outs[True])  # same rng → same sample


def test_device_executor_fused_matches_legacy(stack):
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    infer_fn = _infer(stack)
    seeds = np.arange(10)
    outs = {}
    for fused in (False, True):
        ex = DeviceExecutor(g.device_arrays(), store, fan, infer_fn,
                            max_batch=16, rng_seed=5, fused=fused)
        outs[fused] = np.asarray(ex.run(seeds))
        ex.close()
    assert np.array_equal(outs[False], outs[True])


# ---------------------------------------------------------------------------
# MicroBatcher: coalescing / deadline / budget unit tests
# ---------------------------------------------------------------------------
def _req(i, n_seeds=4):
    return Request(i, np.arange(n_seeds, dtype=np.int64),
                   time.perf_counter())


def test_micro_batcher_coalesces_until_max_seeds():
    mb = MicroBatcher(deadline_s=60.0, max_seeds=12)
    assert mb.add([_req(0)]) is None          # 4 seeds queued
    assert mb.add([_req(1)]) is None          # 8
    out = mb.add([_req(2)])                   # 12 → closes
    assert out is not None and len(out) == 3
    assert mb.emitted == 1 and mb.coalesced == 1
    assert mb.flush() is None                 # state fully reset


def test_micro_batcher_deadline_closes():
    from repro.testing import FakeClock
    clk = FakeClock()
    mb = MicroBatcher(deadline_s=0.01, max_seeds=10**6, clock=clk)
    assert mb.add([_req(0)]) is None
    clk.advance(0.02)
    out = mb.add([_req(1)])                   # deadline hit at add time
    assert out is not None and len(out) == 2


def test_micro_batcher_psgs_budget_closes():
    table = np.full(8, 5.0)
    mb = MicroBatcher(deadline_s=60.0, max_seeds=10**6, psgs_budget=30.0,
                      psgs_table=table)
    assert mb.add([_req(0)]) is None          # 20 PSGS
    out = mb.add([_req(1)])                   # 40 ≥ 30 → closes
    assert out is not None and len(out) == 2


def test_micro_batcher_single_batch_not_counted_coalesced():
    mb = MicroBatcher(deadline_s=60.0, max_seeds=4)
    out = mb.add([_req(0)])                   # closes immediately, 1 source
    assert out is not None
    assert mb.emitted == 1 and mb.coalesced == 0


@pytest.mark.subprocess
def test_sharded_lookup_hops_matches_per_hop():
    """ShardedFeatureStore.lookup_hops (one shard_map exchange for the whole
    sample) must return the same rows as per-hop lookups, regardless of how
    concatenation re-partitions ids over the mesh."""
    from conftest import run_subprocess
    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import (ShardedFeatureStore, TieredFeatureStore,
                        TopologySpec, compute_fap, quiver_placement)
from repro.graph import power_law_graph
n, d, fan = 640, 8, (3, 2)
g = power_law_graph(n, 5.0, seed=0)
feats = np.random.default_rng(1).normal(size=(n, d)).astype(np.float32)
topo = TopologySpec(num_pods=2, devices_per_pod=4, rows_per_device=64,
                    rows_host=128, hot_replicate_fraction=0.2)
store = TieredFeatureStore.build(feats, quiver_placement(
    compute_fap(g, fan), topo))
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
sstore = ShardedFeatureStore.from_tiered(store, mesh, "x")
rng = np.random.default_rng(3)
hops = [jnp.asarray(rng.integers(-1, n, size=s).astype(np.int32))
        for s in (16, 48, 96)]
per_hop = [np.asarray(sstore.lookup(h)) for h in hops]
fused = sstore.lookup_hops(hops)
for a, b in zip(per_hop, fused):
    assert np.array_equal(a, np.asarray(b))
print("SHARDED_FUSED_OK")
"""
    r = run_subprocess(code, devices=8)
    assert "SHARDED_FUSED_OK" in r.stdout, r.stderr


def test_serve_stream_with_micro_batcher(stack):
    """End-to-end: the coalescing stage feeds fewer, larger batches into the
    engine and every request still completes exactly once."""
    g, fan, feats, fap, topo = stack
    store = _fresh_store(stack)
    infer_fn = _infer(stack)
    psgs = compute_psgs(g, fan)
    host = HostExecutor(g, store, fan, infer_fn, psgs_table=psgs)
    engine = ServingEngine({"host": host}, StaticScheduler("host"))
    reqs = [Request(i, np.arange(4, dtype=np.int64), 0.0) for i in range(9)]
    micro = MicroBatcher(deadline_s=60.0, max_seeds=12)
    m = engine.serve_stream(reqs, DynamicBatcher(deadline_s=0.0, max_batch=1),
                            micro=micro)
    assert m.requests == 9
    assert micro.emitted == 3                  # 9 requests → 3 super-batches
    assert micro.coalesced == 3
    assert sum(m.routed.values()) == 3         # engine saw super-batches
    engine.close()
