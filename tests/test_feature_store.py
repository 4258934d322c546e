"""Tiered feature store: exactness across tiers, dedup path, sharded
(shard_map) one-sided reads in a subprocess with 8 fake devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (TieredFeatureStore, TopologySpec, compute_fap,
                        quiver_placement)
from repro.graph import power_law_graph
from tests.conftest import run_subprocess


@pytest.fixture(scope="module")
def store_and_feats():
    n, d = 1500, 24
    g = power_law_graph(n, 6.0, seed=0)
    fap = compute_fap(g, (4, 3))
    feats = np.random.default_rng(1).normal(size=(n, d)).astype(np.float32)
    topo = TopologySpec(num_pods=2, devices_per_pod=4, rows_per_device=64,
                        rows_host=256, hot_replicate_fraction=0.25)
    plan = quiver_placement(fap, topo)
    return TieredFeatureStore.build(feats, plan), feats


def test_lookup_exact_all_tiers(store_and_feats):
    store, feats = store_and_feats
    ids = np.random.default_rng(2).integers(0, feats.shape[0], 128)
    ids[7] = -1
    ids[50] = ids[3]  # duplicate
    out = np.asarray(store.lookup(jnp.asarray(ids, jnp.int32)))
    expected = np.where((ids >= 0)[:, None], feats[np.maximum(ids, 0)], 0.0)
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_lookup_without_dedup_matches(store_and_feats):
    store, feats = store_and_feats
    ids = np.random.default_rng(3).integers(0, feats.shape[0], 64)
    a = np.asarray(store.lookup(jnp.asarray(ids, jnp.int32), dedup=True))
    b = np.asarray(store.lookup(jnp.asarray(ids, jnp.int32), dedup=False))
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_device_only_path_zeroes_cold(store_and_feats):
    store, feats = store_and_feats
    plan = store.plan
    ids = np.arange(feats.shape[0])[::7]
    out = np.asarray(store.lookup(jnp.asarray(ids, jnp.int32),
                                  include_host=False))
    cold = plan.tier[ids] >= 2
    assert np.allclose(out[cold], 0.0)
    np.testing.assert_allclose(out[~cold], feats[ids[~cold]], rtol=1e-6)


def test_tier_histogram(store_and_feats):
    store, feats = store_and_feats
    hist = store.tier_histogram(np.arange(200))
    assert sum(hist.values()) == 200


@pytest.mark.subprocess
def test_sharded_store_one_sided_reads():
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.graph import power_law_graph
from repro.core.fap import compute_fap
from repro.core.placement import TopologySpec, quiver_placement
from repro.core.feature_store import TieredFeatureStore, ShardedFeatureStore
n, d = 2000, 16
g = power_law_graph(n, 8.0, seed=0)
fap = compute_fap(g, (4, 3))
feats = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
topo = TopologySpec(num_pods=2, devices_per_pod=4, rows_per_device=128,
                    rows_host=256, hot_replicate_fraction=0.25)
plan = quiver_placement(fap, topo)
store = TieredFeatureStore.build(feats, plan)
from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
ss = ShardedFeatureStore.from_tiered(store, mesh, "x")
ids = np.random.default_rng(2).integers(0, n, size=8 * 32).astype(np.int32)
tt = plan.tier[ids]
ids = np.where(tt <= 1, ids, -1).astype(np.int32)
out = np.asarray(ss.lookup(jnp.asarray(ids)))
expect = np.where((ids >= 0)[:, None], feats[np.maximum(ids, 0)], 0.0)
assert np.allclose(out, expect, atol=1e-5), np.abs(out - expect).max()
print("SHARDED_OK")
"""
    r = run_subprocess(code, devices=8)
    assert "SHARDED_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.subprocess
def test_sharded_store_cold_rows_exact_not_zeros():
    """Regression: HOST/DISK ids through the sharded store used to resolve
    silently to zeros. The cold fallback must return the exact feature rows
    (bit-identical to the single-host tiered store), count its host fetches,
    and leave -1 padding and HBM-tier rows untouched."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.graph import power_law_graph
from repro.core.fap import compute_fap
from repro.core.placement import TopologySpec, quiver_placement
from repro.core.feature_store import TieredFeatureStore, ShardedFeatureStore
n, d = 2000, 16
g = power_law_graph(n, 8.0, seed=0)
fap = compute_fap(g, (4, 3))
feats = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
topo = TopologySpec(num_pods=2, devices_per_pod=4, rows_per_device=128,
                    rows_host=256, hot_replicate_fraction=0.25)
plan = quiver_placement(fap, topo)
store = TieredFeatureStore.build(feats, plan)
from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
ss = ShardedFeatureStore.from_tiered(store, mesh, "x")
ids = np.random.default_rng(2).integers(0, n, size=8 * 32).astype(np.int32)
ids[5] = -1                                  # padding stays zero
assert (plan.tier[np.maximum(ids, 0)] >= 2).any()   # cold really sampled
out = np.asarray(ss.lookup(jnp.asarray(ids)))
want = np.asarray(store.lookup(jnp.asarray(ids)))   # single-host reference
assert np.array_equal(out, want), np.abs(out - want).max()
expect = np.where((ids >= 0)[:, None], feats[np.maximum(ids, 0)], 0.0)
assert np.allclose(out, expect, atol=1e-5)
assert ss.stats["host_fetches"] > 0 and ss.stats["cold_rows"] > 0
print("SHARDED_COLD_OK")
"""
    r = run_subprocess(code, devices=8)
    assert "SHARDED_COLD_OK" in r.stdout, r.stderr[-2000:]
