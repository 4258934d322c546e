"""Sharded store as a hierarchy member: the owner-sorted dedup exchange,
per-shard staging/spill, shape preconditions and the migration race —
world-8 paths in subprocesses, world-1 staging/executor paths in-process."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import (Prefetcher, ShardedFeatureStore, TieredFeatureStore,
                        TopologySpec, compute_fap, quiver_placement)
from repro.core.placement import TIER_HOST
from repro.graph import power_law_graph
from tests.conftest import run_subprocess

# Shared subprocess preamble: a tiered store with real HOST/DISK tiers and
# the sharded views over an 8-device mesh.
_SETUP = """
import numpy as np, jax, jax.numpy as jnp, tempfile, os
from repro.graph import power_law_graph
from repro.core.fap import compute_fap
from repro.core.placement import TopologySpec, quiver_placement
from repro.core.feature_store import TieredFeatureStore, ShardedFeatureStore
from repro.core.prefetch import Prefetcher
from jax.sharding import AxisType
n, d = 2400, 16
g = power_law_graph(n, 8.0, seed=0)
fap = compute_fap(g, (4, 3))
feats = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
topo = TopologySpec(num_pods=2, devices_per_pod=4, rows_per_device=96,
                    rows_host=300, hot_replicate_fraction=0.25)
plan = quiver_placement(fap, topo)
store = TieredFeatureStore.build(feats, plan)
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
"""


@pytest.mark.subprocess
def test_dedup_exchange_bit_identical_world8():
    """The alltoall exchange on a real 8-device mesh: bit-identical to
    per-hop lookups, to the allgather strategy and to the single-host
    store — cross-hop duplicates, -1 padding and HOST/DISK ids included,
    staged (per-shard spill files) and unstaged; a neighbor duplicated
    across hops is exchanged once (``exchanged_ids`` asserted)."""
    code = _SETUP + """
from repro.core.placement import TIER_WARM
spill_dir = tempfile.mkdtemp()
base = ShardedFeatureStore.from_tiered(store, mesh, "x",
                                       strategy="allgather")
ss = ShardedFeatureStore.from_tiered(store, mesh, "x", strategy="alltoall",
                                     spill_dir=spill_dir)
rng = np.random.default_rng(3)
hops = [rng.integers(0, n, size=s).astype(np.int32) for s in (16, 64, 256)]
hops[1][:8] = hops[0][:8]          # cross-hop duplicates
hops[2][:32] = hops[1][:32]
hops[0][3] = -1                    # padding
want = [np.asarray(store.lookup(jnp.asarray(h))) for h in hops]

def check(s, label):
    fused = s.lookup_hops([jnp.asarray(h) for h in hops])
    per = [s.lookup(jnp.asarray(h)) for h in hops]
    for k in range(len(hops)):
        assert np.array_equal(want[k], np.asarray(fused[k])), (label, k)
        assert np.array_equal(want[k], np.asarray(per[k])), (label, k)

check(base, "allgather")
check(ss, "alltoall")
pf = Prefetcher(ss, budget=n)
assert pf.refresh(scores=np.maximum(fap, 1e-12)) > 0
check(ss, "alltoall+staged")

# dedup accounting: distinct (device, id) pairs only, strictly below the
# raw occurrence count (the duplicates above guarantee a gap)
ss.reset_stats()
ss.lookup_hops([jnp.asarray(h) for h in hops])
st = ss.reset_stats()
cat = np.concatenate(hops).astype(np.int64)
dev = np.repeat(np.arange(8), cat.size // 8)
tier = ss.tier_table_host[np.maximum(cat, 0)]
elig = (cat >= 0) & ((tier == TIER_WARM) | (tier >= 2))  # all cold staged
distinct = len(set(zip(dev[elig].tolist(), cat[elig].tolist())))
assert st["exchanges"] == 1, st
assert st["exchanged_ids"] == distinct, (st, distinct)
assert distinct < int(elig.sum()), (distinct, int(elig.sum()))
assert st["host_fetches"] == 0 and st["stage_misses"] == 0, st
assert st["stage_hits"] > 0, st
print("DEDUP_OK")
"""
    r = run_subprocess(code, devices=8)
    assert "DEDUP_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.subprocess
def test_hop_length_and_ragged_warm_validation_world8():
    """Shape preconditions fail fast with clear ValueErrors, never inside
    shard_map: a hop whose length is not a multiple of the world size, an
    empty hop list, and a ragged warm buffer at construction."""
    code = _SETUP + """
ss = ShardedFeatureStore.from_tiered(store, mesh, "x")
for bad in (20, 0):
    try:
        ss.lookup_hops([np.zeros(32, np.int32), np.zeros(bad, np.int32)])
        raise AssertionError(f"hop of {bad} did not raise")
    except ValueError as e:
        assert "multiple of the mesh world size" in str(e), e
try:
    ss.lookup(np.zeros(13, np.int32))
    raise AssertionError("ragged lookup did not raise")
except ValueError as e:
    assert "multiple of the mesh world size" in str(e), e
try:
    ss.lookup_hops([])
    raise AssertionError("empty hops did not raise")
except ValueError as e:
    assert "at least one hop" in str(e), e
try:
    ShardedFeatureStore(mesh, "x", np.zeros((4, d), np.float32),
                        np.zeros((42, d), np.float32),  # 42 % 8 != 0
                        np.zeros(n, np.int32), np.zeros(n, np.int32),
                        np.zeros(n, np.int32))
    raise AssertionError("ragged warm did not raise")
except ValueError as e:
    assert "divisible by the mesh world size" in str(e), e
print("VALIDATION_OK")
"""
    r = run_subprocess(code, devices=8)
    assert "VALIDATION_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.subprocess
def test_dedup_exchange_under_migration_race_world8():
    """The migration-race harness, pointed at the dedup exchange: a thread
    hammers ``swap_assignments`` on the *source* store while sharded
    lookups run. The sharded tables are build-time copies and rows travel
    with nodes, so every lookup stays bit-identical to the features."""
    code = _SETUP + """
import threading
from repro.core.placement import migration_pairs
ss = ShardedFeatureStore.from_tiered(store, mesh, "x",
                                     strategy="alltoall")
stop = threading.Event()
def churn():
    rng = np.random.default_rng(9)
    while not stop.is_set():
        p0 = rng.dirichlet(np.ones(n))
        f2 = compute_fap(g, (4, 3), seed_prob=p0)
        target = quiver_placement(f2, topo)
        pairs = migration_pairs(store.plan.tier, target.tier, f2, budget=32)
        store.swap_assignments(pairs)
t = threading.Thread(target=churn)
t.start()
try:
    rng = np.random.default_rng(5)
    for _ in range(12):
        hops = [rng.integers(-1, n, size=s).astype(np.int32)
                for s in (32, 128)]
        hops[1][:16] = hops[0][:16]
        outs = ss.lookup_hops([jnp.asarray(h) for h in hops])
        for h, o in zip(hops, outs):
            expect = np.where((h >= 0)[:, None],
                              feats[np.maximum(h, 0)], 0.0)
            assert np.allclose(np.asarray(o), expect, atol=1e-5)
finally:
    stop.set(); t.join()
print("RACE_OK")
"""
    r = run_subprocess(code, devices=8, timeout=420)
    assert "RACE_OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# In-process (world-1 mesh) paths
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def world1_stack(tmp_path_factory):
    n, d = 600, 12
    g = power_law_graph(n, 6.0, seed=0)
    fap = compute_fap(g, (3, 2))
    feats = np.random.default_rng(1).normal(size=(n, d)).astype(np.float32)
    topo = TopologySpec(num_pods=1, devices_per_pod=1, rows_per_device=64,
                        rows_host=150, hot_replicate_fraction=0.25)
    store = TieredFeatureStore.build(feats, quiver_placement(fap, topo))
    mesh = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))
    spill_dir = str(tmp_path_factory.mktemp("shard_spill"))
    ss = ShardedFeatureStore.from_tiered(store, mesh, "x",
                                         spill_dir=spill_dir)
    return g, feats, fap, store, mesh, ss


def test_publish_stage_rebins_global_layout(world1_stack):
    """`publish_stage` accepts the prefetcher's global (N,) id→row layout,
    re-bins it per shard, and the exchange then serves staged cold ids
    from device with zero host fetches."""
    g, feats, fap, store, mesh, ss = world1_stack
    tier = ss.tier_table_host
    cold = np.flatnonzero(tier >= TIER_HOST)[:40]
    assert cold.size > 0
    stage_slot = np.full(feats.shape[0], -1, np.int32)
    stage_slot[cold] = np.arange(cold.size, dtype=np.int32)
    ss.publish_stage(stage_slot, jnp.asarray(feats[cold]))
    assert ss.staged_rows() == cold.size
    ss.reset_stats()
    out = np.asarray(ss.lookup(jnp.asarray(cold.astype(np.int32))))
    np.testing.assert_allclose(out, feats[cold], atol=1e-6)
    st = ss.reset_stats()
    assert st["stage_hits"] == cold.size and st["host_fetches"] == 0, st
    ss.publish_stage(None, None)
    assert ss.staged_rows() == 0


def test_spill_files_serve_disk_rows(world1_stack):
    """Per-shard spill files answer DISK reads through read_cold_rows
    (counted as spill_reads) with the exact feature values."""
    g, feats, fap, store, mesh, ss = world1_stack
    disk = np.flatnonzero(ss.tier_table_host == 3)
    if disk.size == 0:
        pytest.skip("placement produced no DISK tier at this size")
    ss.reset_stats()
    rows = ss.read_cold_rows(disk[:16])
    np.testing.assert_allclose(rows, feats[disk[:16]], atol=1e-6)
    assert ss.snapshot_stats()["spill_reads"] == min(disk.size, 16)


def test_fuse_aggregate_downgrade_warns_once(world1_stack):
    """ShardedExecutor accepts fuse_aggregate=True for construction-site
    symmetry but emits one RuntimeWarning and falls back to the fused
    path; collect_mode reports the active mode."""
    from repro.serving.executors import ShardedExecutor
    g, feats, fap, store, mesh, ss = world1_stack

    def infer_fn(hop_feats, hop_ids, deep_agg=None):
        return hop_feats[0]

    ShardedExecutor._warned_fuse_aggregate = False
    with pytest.warns(RuntimeWarning, match="fuse_aggregate=True has no"):
        ex = ShardedExecutor(mesh, "x", g.device_arrays(), ss, (3, 2),
                             infer_fn, max_batch=16, fuse_aggregate=True)
    assert ex.collect_mode(ss) == "fused"  # downgraded, and visible
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")    # second construction: silent
        ex2 = ShardedExecutor(mesh, "x", g.device_arrays(), ss, (3, 2),
                              infer_fn, max_batch=16, fuse_aggregate=True)
    assert not [w for w in rec if "fuse_aggregate" in str(w.message)]
    assert ex2.collect_mode(ss) == "fused"


def test_collect_mode_strings_cover_matrix(world1_stack):
    """collect_mode maps the (flags, store capability) matrix exactly."""
    from repro.serving.executors import HostExecutor
    g, feats, fap, store, mesh, ss = world1_stack

    def infer_fn(hop_feats, hop_ids, deep_agg=None):
        return hop_feats[0]

    host = HostExecutor(g, store, (3, 2), infer_fn, fused=True,
                        fuse_aggregate=True)
    assert host.collect_mode(store) == "fuse_aggregate"
    assert host.collect_mode(ss) == "fused"  # sharded: no lookup_aggregate
    host2 = HostExecutor(g, store, (3, 2), infer_fn, fused=False)
    assert host2.collect_mode(store) == "per_hop"
