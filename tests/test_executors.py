"""Executor-graph serving stack: pluggable executors, N-way cost routing
(and its reduction to the paper's binary PSGS threshold), admission control,
the no-silent-truncation regression, batcher/padding boundary cases, and the
3-executor (host+device+sharded) integration path."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DynamicBatcher, Request, TieredFeatureStore,
                        TopologySpec, WorkloadGenerator, compute_fap,
                        compute_psgs, quiver_placement)
from repro.graph import power_law_graph
from repro.models.gnn_basic import sage_init, sage_layered
from repro.serving import (POLICIES, CalibrationResult, CostModelRouter,
                           DeviceExecutor, Executor, HostExecutor,
                           HybridScheduler, LatencyCurve, ServingEngine,
                           StaticScheduler, calibrate_executors,
                           pad_to_bucket)
from tests.conftest import run_subprocess


# ---------------------------------------------------------------------------
# pad_to_bucket edge cases (satellite: serving-layer coverage)
# ---------------------------------------------------------------------------
def test_pad_to_bucket_empty_array():
    out = pad_to_bucket(np.empty((0,), np.int32), min_size=8)
    assert out.shape == (8,) and (out == -1).all()


def test_pad_to_bucket_exact_power_of_two():
    a = np.arange(32, dtype=np.int64)
    out = pad_to_bucket(a, min_size=4)
    assert out.shape == (32,) and (out == a).all()


def test_pad_to_bucket_reexported_from_core():
    from repro.core import pad_to_bucket as core_pad
    from repro.core.serving import pad_to_bucket as serving_pad
    assert core_pad is pad_to_bucket and serving_pad is pad_to_bucket


# ---------------------------------------------------------------------------
# DynamicBatcher boundaries
# ---------------------------------------------------------------------------
def test_dynamic_batcher_zero_deadline_closes_each_add():
    b = DynamicBatcher(deadline_s=0.0, max_batch=100)
    for i in range(4):
        out = b.add(Request(i, np.array([i]), time.perf_counter()))
        assert out is not None and len(out) == 1
    assert b.flush() is None


def test_dynamic_batcher_exact_psgs_budget_boundary():
    table = np.full(10, 10.0, np.float32)
    b = DynamicBatcher(deadline_s=10.0, psgs_budget=30.0, max_batch=100,
                       psgs_table=table)
    assert b.add(Request(0, np.array([0]), time.perf_counter())) is None
    assert b.add(Request(1, np.array([1]), time.perf_counter())) is None
    out = b.add(Request(2, np.array([2]), time.perf_counter()))
    assert out is not None and len(out) == 3  # 30 >= 30: budget is inclusive


def test_dynamic_batcher_padded_seed_ids_do_not_count():
    table = np.full(10, 10.0, np.float32)
    b = DynamicBatcher(deadline_s=10.0, psgs_budget=25.0, max_batch=100,
                       psgs_table=table)
    r = Request(0, np.array([1, -1, -1, 2]), time.perf_counter())
    assert b.add(r) is None  # only the two valid seeds (20.0) accumulate


# ---------------------------------------------------------------------------
# Serving stack fixture
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def stack():
    n, d, fan = 1200, 16, (4, 3)
    g = power_law_graph(n, 6.0, seed=0)
    feats = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
    fap = compute_fap(g, fan)
    topo = TopologySpec(num_pods=1, devices_per_pod=1, rows_per_device=400,
                        rows_host=600, hot_replicate_fraction=0.3)
    store = TieredFeatureStore.build(feats, quiver_placement(fap, topo))
    params = sage_init(jax.random.key(0), [d, 32, 32])

    @jax.jit
    def infer_fn(hop_feats, hop_ids):
        masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
        return sage_layered(params, hop_feats, fan, hop_masks=masks)

    psgs = compute_psgs(g, fan)
    return dict(graph=g, store=store, fan=fan, infer_fn=infer_fn, psgs=psgs)


def _executors(stack, *, max_batch=16, capacity=1):
    g = stack["graph"]
    return {
        "host": HostExecutor(g, stack["store"], stack["fan"],
                             stack["infer_fn"], capacity=capacity,
                             psgs_table=stack["psgs"]),
        "device": DeviceExecutor(g.device_arrays(), stack["store"],
                                 stack["fan"], stack["infer_fn"],
                                 max_batch=max_batch, capacity=capacity,
                                 psgs_table=stack["psgs"]),
    }


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------
def test_executor_protocol_and_futures(stack):
    ex = _executors(stack)
    for e in ex.values():
        assert isinstance(e, Executor)
        assert e.cost(np.array([1, 2, -1])) > 0
    fut = ex["device"].submit(np.arange(4))
    out = np.asarray(fut.result())
    assert out.shape[0] == 4 and np.isfinite(out).all()


def test_device_executor_chunks_oversized_batch_no_silent_drop(stack):
    """Regression: the old _device_path zero-filled max_batch and dropped
    every seed beyond it; oversized batches must chunk instead."""
    ex = _executors(stack, max_batch=8)["device"]
    seeds = np.arange(20)
    out = np.asarray(ex.process(seeds))
    assert out.shape[0] == 20  # one row per seed, nothing truncated
    assert np.isfinite(out).all()
    # seeds beyond the old cutoff produce real (not zero-filled) outputs
    assert np.abs(out[8:]).sum() > 0


def test_legacy_engine_serves_request_larger_than_max_batch(stack):
    """End-to-end no-drop regression through the legacy shim engine."""
    from repro.core.pipeline import ServingEngine as LegacyEngine
    engine = LegacyEngine(stack["graph"], stack["store"], stack["fan"],
                          stack["infer_fn"], StaticScheduler("device"),
                          num_workers=1, max_batch=8)
    out = np.asarray(engine._device_path(np.arange(20)))
    assert out.shape[0] == 20
    req = Request(0, np.arange(20), time.perf_counter())
    m = engine.run([[req]])
    assert m.requests == 1 and m.summary()["routed_device"] == 1


# ---------------------------------------------------------------------------
# N-way router ↔ binary threshold reduction
# ---------------------------------------------------------------------------
def _binary_calib():
    q = np.linspace(1, 100, 400)
    host_lat = 1e-4 * q                      # linear in work
    dev_lat = 2e-3 + 1e-5 * q                # offset + shallow slope
    return CalibrationResult(host=LatencyCurve.fit(q, host_lat, bins=8),
                             device=LatencyCurve.fit(q, dev_lat, bins=8))


@pytest.mark.parametrize("policy", POLICIES)
def test_cost_router_reduces_to_threshold_rule(policy):
    calib = _binary_calib()
    table = np.linspace(1, 100, 200)  # psgs_table: seed i costs table[i]
    hybrid = HybridScheduler(table, calib.threshold(policy), policy)
    router = CostModelRouter.from_calibration(table, calib, policy)
    for i in range(0, 200, 3):
        seeds = np.array([i])
        assert hybrid.route(seeds) == router.route(seeds), (policy, i)
    assert hybrid.routed == router.routed


def test_engine_nway_matches_binary_engine_routing(stack):
    """Integration: with only host+device registered, the cost-model engine
    routes exactly like the paper's binary PSGS-threshold engine."""
    psgs = stack["psgs"]
    gen = WorkloadGenerator(stack["graph"].num_nodes,
                            stack["graph"].out_degree, seed=3)
    reqs = list(gen.stream(24, seeds_per_request=4))
    costs = [float(psgs[r.seeds].sum()) for r in reqs]
    mid = float(np.median(costs)) * 1.01  # avoid an exact-boundary tie
    cmax = max(costs) + 1.0
    curves = {
        "host": LatencyCurve(psgs=np.array([0.0, cmax]),
                             avg=np.array([0.0, cmax]),
                             mx=np.array([0.0, cmax])),
        "device": LatencyCurve(psgs=np.array([0.0, cmax]),
                               avg=np.array([mid, mid]),
                               mx=np.array([mid, mid])),
    }
    calib = CalibrationResult(host=curves["host"], device=curves["device"])
    thr = calib.threshold("latency_preferred")

    m_bin = ServingEngine(_executors(stack),
                          HybridScheduler(psgs, thr)).run([[r] for r in reqs])
    m_nway = ServingEngine(
        _executors(stack),
        CostModelRouter.from_curves(psgs, curves, "latency_preferred")
    ).run([[r] for r in reqs])
    assert m_bin.requests == m_nway.requests == 24
    assert m_bin.routed == m_nway.routed
    assert m_bin.routed_host > 0 and m_bin.routed_device > 0


def test_calibrate_executors_fits_curve_per_executor(stack):
    ex = _executors(stack)
    batches = [np.arange(i, i + 4) for i in (0, 40, 80)]
    curves = calibrate_executors(ex, batches, stack["psgs"], repeats=1,
                                 warmup=1)
    assert set(curves) == {"host", "device"}
    for c in curves.values():
        assert c.psgs.size >= 1 and (c.avg > 0).all()


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------
def test_engine_shed_policy_drops_over_window(stack):
    slow = dict(stack)
    base = stack["infer_fn"]

    def slow_infer(hop_feats, hop_ids):
        out = base(hop_feats, hop_ids)
        jax.block_until_ready(out)
        time.sleep(0.15)
        return out

    slow["infer_fn"] = slow_infer
    engine = ServingEngine(_executors(slow), StaticScheduler("host"),
                           max_inflight=1, admission="shed")
    reqs = [Request(i, np.array([i]), time.perf_counter()) for i in range(6)]
    m = engine.run([[r] for r in reqs])
    assert m.shed >= 1
    assert m.requests + m.shed == 6
    assert m.summary()["shed"] == m.shed


def test_engine_wait_policy_serves_everything(stack):
    engine = ServingEngine(_executors(stack), StaticScheduler("host"),
                           max_inflight=1, admission="wait")
    reqs = [Request(i, np.array([i]), time.perf_counter()) for i in range(5)]
    m = engine.run([[r] for r in reqs])
    assert m.shed == 0 and m.requests == 5


def test_engine_propagates_executor_failure(stack):
    bad = dict(stack)

    def boom(hop_feats, hop_ids):
        raise RuntimeError("executor exploded")

    bad["infer_fn"] = boom
    engine = ServingEngine(_executors(bad), StaticScheduler("device"))
    with pytest.raises(RuntimeError, match="executor exploded"):
        engine.run([[Request(0, np.array([1]), time.perf_counter())]])


def test_engine_releases_window_when_router_raises(stack):
    """Regression: a router failure must not leak an admission permit."""
    class FlakyRouter:
        def __init__(self):
            self.calls = 0

        def route(self, seeds):
            self.calls += 1
            if self.calls == 1:
                raise IndexError("bad seed id")
            return "host"

    engine = ServingEngine(_executors(stack), FlakyRouter(), max_inflight=1)
    with pytest.raises(IndexError):
        engine.submit_batch([Request(0, np.array([0]),
                                     time.perf_counter())])
    # with the permit leaked this run() would deadlock on the window
    m = engine.run([[Request(1, np.array([1]), time.perf_counter())]])
    assert m.requests == 1


def test_empty_summary_reports_zeroed_not_perfect_profile():
    from repro.serving import ServeMetrics
    s = ServeMetrics(shed=5).summary()
    assert s["requests"] == 0 and s["shed"] == 5
    assert s["p50_ms"] == 0.0
    assert s["pct_in_400ms"] == 0.0  # must not claim a met SLO for 0 served


def test_router_skips_unsupported_executor():
    table = np.full(8, 10.0, np.float32)
    flat = LatencyCurve(psgs=np.array([0.0, 100.0]),
                        avg=np.array([1.0, 1.0]), mx=np.array([1.0, 1.0]))
    slow = LatencyCurve(psgs=np.array([0.0, 100.0]),
                        avg=np.array([9.0, 9.0]), mx=np.array([9.0, 9.0]))

    class Fake:
        kind = "device"
        capacity = 1
        inflight = 0

        def __init__(self, ok):
            self.ok = ok

        def supports(self, seeds):
            return self.ok

    router = CostModelRouter(table, "latency_preferred")
    router.register("cheap", flat, executor=Fake(ok=False))
    router.register("pricey", slow, executor=Fake(ok=True))
    assert router.route(np.array([0])) == "pricey"  # cheap is ineligible
    # nothing supports the batch → degrade to considering every executor
    router2 = CostModelRouter(table, "latency_preferred")
    router2.register("a", flat, executor=Fake(ok=False))
    router2.register("b", slow, executor=Fake(ok=False))
    assert router2.route(np.array([0])) == "a"


def test_metrics_clean_after_failed_run(stack):
    """Stragglers/accounting from a failed run must not pollute the next
    run's ServeMetrics, and drain must not swallow late failures."""
    flaky = dict(stack)
    base = stack["infer_fn"]
    fail = {"on": True}

    def maybe_boom(hop_feats, hop_ids):
        if fail["on"]:
            time.sleep(0.05)  # fail after the run loop has moved on
            raise RuntimeError("flaky")
        return base(hop_feats, hop_ids)

    flaky["infer_fn"] = maybe_boom
    engine = ServingEngine(_executors(flaky, capacity=2),
                           StaticScheduler("host"))
    reqs = [Request(i, np.array([i]), time.perf_counter()) for i in range(4)]
    with pytest.raises(RuntimeError, match="flaky"):
        engine.run([[r] for r in reqs])
    fail["on"] = False
    m = engine.run([[Request(9, np.array([9]), time.perf_counter())]])
    assert m.requests == 1 and len(m.latencies) == 1
    assert m.routed == {"host": 1}


# ---------------------------------------------------------------------------
# 3-executor integration: host + device + sharded over a CPU mesh
# ---------------------------------------------------------------------------
@pytest.mark.subprocess
def test_three_executor_engine_with_sharded_mesh():
    code = """
import time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core import (TieredFeatureStore, TopologySpec, compute_fap,
                        compute_psgs, quiver_placement)
from repro.core.feature_store import ShardedFeatureStore
from repro.core.serving import Request
from repro.graph import power_law_graph
from repro.models.gnn_basic import sage_init, sage_layered
from repro.serving import (CostModelRouter, DeviceExecutor, HostExecutor,
                           LatencyCurve, ServingEngine, ShardedExecutor)

n, d, fan = 2000, 16, (4, 3)
g = power_law_graph(n, 8.0, seed=0)
fap = compute_fap(g, fan)
psgs = compute_psgs(g, fan)
feats = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
topo = TopologySpec(num_pods=2, devices_per_pod=4, rows_per_device=128,
                    rows_host=256, hot_replicate_fraction=0.25)
store = TieredFeatureStore.build(feats, quiver_placement(fap, topo))
mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
sstore = ShardedFeatureStore.from_tiered(store, mesh, "x")
params = sage_init(jax.random.key(0), [d, 32, 32])

@jax.jit
def infer_fn(hop_feats, hop_ids):
    masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
    return sage_layered(params, hop_feats, fan, hop_masks=masks)

gd = g.device_arrays()
ex = {
    "host": HostExecutor(g, store, fan, infer_fn, psgs_table=psgs),
    "device": DeviceExecutor(gd, store, fan, infer_fn, max_batch=16,
                             psgs_table=psgs),
    "sharded": ShardedExecutor(mesh, "x", gd, sstore, fan, infer_fn,
                               max_batch=16, psgs_table=psgs),
}
# the sharded executor's static shape is a multiple of the mesh world
assert ex["sharded"].max_batch % 8 == 0

# give each executor a sweet spot at a real workload cost so N-way routing
# provably exercises all three
order = np.argsort(psgs)
s_lo, s_mid, s_hi = int(order[0]), int(order[n // 2]), int(order[-1])
p_lo, p_mid, p_hi = (float(psgs[s]) for s in (s_lo, s_mid, s_hi))
assert p_lo < p_mid < p_hi
qmax = p_hi + 1.0

def vcurve(center):
    xs = np.array([0.0, center, qmax])
    ys = np.abs(xs - center) + 1e-6
    return LatencyCurve(psgs=xs, avg=ys, mx=ys)

router = CostModelRouter(psgs, "latency_preferred")
router.register("host", vcurve(p_lo), kind="host", executor=ex["host"])
router.register("device", vcurve(p_mid), executor=ex["device"])
router.register("sharded", vcurve(p_hi), executor=ex["sharded"])

engine = ServingEngine(ex, router, max_inflight=8)
reqs = [Request(i, np.array([s]), time.perf_counter())
        for i, s in enumerate([s_lo, s_mid, s_hi] * 4)]
m = engine.run([[r] for r in reqs])
assert m.requests == 12, m.requests
assert all(m.routed.get(k, 0) == 4 for k in ("host", "device", "sharded")), \\
    m.routed

# the sharded path itself chunks oversized batches and returns finite rows
out = np.asarray(ex["sharded"].run(np.arange(24)))
assert out.shape == (24, 32) and np.isfinite(out).all()
print("THREE_EXEC_OK", m.routed)
"""
    r = run_subprocess(code, devices=8)
    assert "THREE_EXEC_OK" in r.stdout, r.stderr[-3000:]
