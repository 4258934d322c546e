"""In-program tracing (``repro.tracing``): off by default and free when off;
on, one served request gives the serving path's span tree with the
request's id on every span; the store's ``host_reads`` counter; the compile
counter; the record cap; and the program's stamps on the profiler's clock."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import (Request, TieredFeatureStore, TopologySpec,
                        compute_fap, compute_psgs, quiver_placement)
from repro.core.placement import TIER_HOST
from repro.graph import power_law_graph
from repro.models.gnn_basic import sage_init, sage_layered
from repro.serving import (DeviceExecutor, HostExecutor, ServingEngine,
                           StaticScheduler)


@pytest.fixture(autouse=True)
def tracing_off():
    """Every test starts and ends with tracing off and nothing kept."""
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


@pytest.fixture(scope="module")
def stack():
    n, d, fan = 1200, 16, (4, 3)
    g = power_law_graph(n, 6.0, seed=0)
    feats = np.random.default_rng(0).normal(size=(n, d)).astype(np.float32)
    fap = compute_fap(g, fan)
    topo = TopologySpec(num_pods=1, devices_per_pod=1, rows_per_device=400,
                        rows_host=600, hot_replicate_fraction=0.3)
    plan = quiver_placement(fap, topo)
    store = TieredFeatureStore.build(feats, plan)
    params = sage_init(jax.random.key(0), [d, 32, 32])

    @jax.jit
    def infer_fn(hop_feats, hop_ids):
        masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
        return sage_layered(params, hop_feats, fan, hop_masks=masks)

    psgs = compute_psgs(g, fan)
    executors = {
        "host": HostExecutor(g, store, fan, infer_fn, psgs_table=psgs),
        "device": DeviceExecutor(g.device_arrays(), store, fan, infer_fn,
                                 max_batch=16, psgs_table=psgs),
    }
    tier = np.asarray(plan.tier)
    yield dict(store=store, executors=executors, tier=tier)
    for ex in executors.values():
        ex.close()


def _serve(stack, dest: str, req_id: int, seeds) -> np.ndarray:
    engine = ServingEngine(stack["executors"], StaticScheduler(dest),
                           max_inflight=4)
    fut = engine.submit_batch([Request(req_id, np.asarray(seeds), 0.0)])
    out = np.asarray(fut.result(timeout=120))
    engine.drain()
    return out


def test_off_by_default_and_free(stack):
    assert not tracing.enabled()
    a = tracing.span("store.gather")
    assert a is tracing.span("engine.submit", req=3, kind="x")
    with a:
        pass
    assert tracing.current() is None
    fn = stack["executors"]["device"].run
    assert tracing.in_lane(fn) is fn
    _serve(stack, "device", 1, [1, 2, 3])
    assert tracing.drain() == []
    assert tracing.compiles() == 0 and tracing.dropped() == 0


def _by_id(records):
    return {r.span_id: r for r in records}


def _ancestors(rec, by_id):
    out = []
    while rec.parent is not None:
        rec = by_id[rec.parent]
        out.append(rec.name)
    return out


@pytest.mark.parametrize("dest", ["device", "host"])
def test_one_request_gives_the_span_tree(stack, dest):
    tier = stack["tier"]
    hbm = np.flatnonzero(tier < TIER_HOST)[:5]
    _serve(stack, dest, 0, hbm)          # warm: compile outside the trace
    tracing.enable()
    _serve(stack, dest, 41, hbm)
    tracing.disable()
    recs = tracing.drain()
    by_id = _by_id(recs)
    names = {r.name for r in recs}
    assert {"engine.submit", "engine.admit", "router.route", "lane.queue",
            "executor.run", "executor.sample", "executor.collect",
            "executor.infer", "executor.sync", "store.lookup_hops",
            "store.dedup", "store.gather", "store.cold",
            "store.read"} <= names
    assert all(r.req == 41 for r in recs)
    want_parent = {
        "engine.admit": "engine.submit", "router.route": "engine.submit",
        "lane.queue": "engine.submit", "executor.run": "engine.submit",
        "executor.sample": "executor.run", "executor.collect": "executor.run",
        "executor.infer": "executor.run", "executor.sync": "executor.run",
        "store.lookup_hops": "executor.collect",
        "store.dedup": "store.lookup_hops",
        "store.gather": "store.lookup_hops",
        "store.cold": "store.lookup_hops",
        "store.read": "store.cold"}
    for r in recs:
        assert r.end_ns >= r.start_ns and r.cpu_ns >= 0
        if r.name == "engine.submit":
            assert r.parent is None
            continue
        parent = by_id[r.parent]
        assert parent.name == want_parent[r.name], (r.name, parent.name)
        # a child starts inside its parent; on the parent's own thread it
        # also ends inside it (the lane outlives the submitting call)
        assert parent.start_ns <= r.start_ns
        if r.thread == parent.thread and r.name != "lane.queue":
            assert r.end_ns <= parent.end_ns
    assert [r.attrs for r in recs if r.name == "executor.run"] == [
        {"kind": dest}]
    lane = next(r for r in recs if r.name == "lane.queue")
    run = next(r for r in recs if r.name == "executor.run")
    assert lane.end_ns <= run.start_ns and lane.thread == run.thread


def test_host_fetch_callback_parents_to_its_dispatch(stack):
    tier = stack["tier"]
    ids = np.concatenate([np.flatnonzero(tier == TIER_HOST)[:3],
                          np.flatnonzero(tier < TIER_HOST)[:3]])
    _serve(stack, "device", 0, ids)
    tracing.enable()
    _serve(stack, "device", 7, ids)
    tracing.disable()
    recs = tracing.drain()
    by_id = _by_id(recs)
    cbs = [r for r in recs if r.name == "store.callback"]
    assert cbs and all(r.req == 7 for r in cbs)
    for cb in cbs:
        fetch = by_id[cb.parent]
        assert fetch.name == "store.host_fetch"
        assert _ancestors(fetch, by_id)[:3] == [
            "store.cold", "store.lookup_hops", "executor.collect"]
        assert cb.start_ns >= fetch.start_ns


def test_host_reads_counts_each_read_of_a_lookup(stack):
    store, tier = stack["store"], stack["tier"]
    hbm = jnp.asarray(np.flatnonzero(tier < TIER_HOST)[:8], jnp.int32)
    store.reset_stats()
    store.lookup_hops([hbm, hbm[:4]])
    assert store.snapshot_stats()["host_reads"] == 2   # _resolve_cold's two
    store.lookup_hops([hbm], include_host=False)
    assert store.snapshot_stats()["host_reads"] == 2   # no cold check
    store.lookup(hbm)
    assert store.snapshot_stats()["host_reads"] == 4
    tracing.enable()
    store.lookup_aggregate([hbm[:2], hbm])
    tracing.disable()
    reads = [r for r in tracing.drain() if r.name == "store.read"]
    # uniq, tier table, slot table, innermost hop, inverse (nothing cold)
    assert store.snapshot_stats()["host_reads"] == 4 + 5 == 4 + len(reads)


def test_compile_counter_counts_a_fresh_shape():
    f = jax.jit(lambda x: x * 2 + 1)
    x3, x5, x7 = (np.ones(n, np.float32) for n in (3, 5, 7))
    f(x3).block_until_ready()
    tracing.enable()
    f(x3).block_until_ready()                      # cached: no compile
    assert tracing.compiles() == 0
    f(x5).block_until_ready()                      # fresh shape
    assert tracing.compiles() == 1
    tracing.disable()
    f(x7).block_until_ready()                      # off: not counted
    assert tracing.compiles() == 1
    tracing.drain()
    assert tracing.compiles() == 0


def test_cap_counts_drops(monkeypatch):
    monkeypatch.setattr(tracing, "CAP", 3)
    tracing.enable()
    for i in range(5):
        with tracing.span("store.read", req=i):
            pass
    tracing.disable()
    assert tracing.dropped() == 2
    recs = tracing.drain()
    assert [r.req for r in recs] == [0, 1, 2]
    assert tracing.dropped() == 0


def test_spans_are_in_the_profilers_host_plane_on_its_clock(stack, tmp_path):
    """Each span is a profiler annotation, and the record's wall-clock
    stamps sit where the trace puts the annotation (``start_ns`` of a
    host-plane event counts from the profile's ``profile_start_time``)."""
    from jax.profiler import ProfileData

    store, tier = stack["store"], stack["tier"]
    hbm = jnp.asarray(np.flatnonzero(tier < TIER_HOST)[:8], jnp.int32)
    store.lookup_hops([hbm])
    jax.profiler.start_trace(str(tmp_path))
    tracing.enable()
    for i in range(20):
        with tracing.span("engine.submit", req=i):
            store.lookup_hops([hbm])
    tracing.disable()
    jax.profiler.stop_trace()
    recs = tracing.drain()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    start = None
    events: dict[str, list] = {}
    for plane in data.planes:
        stats = dict(plane.stats or ())
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns)))
    assert start is not None
    offs = []
    for name in ("engine.submit", "store.lookup_hops", "store.dedup",
                 "store.gather", "store.cold", "store.read"):
        mine = sorted((r.start_ns - start, r.end_ns - start)
                      for r in recs if r.name == name)
        theirs = sorted(events[name])
        assert len(mine) == len(theirs) == (40 if name == "store.read"
                                            else 20)
        offs += [(m[0] - t[0], m[1] - t[1]) for m, t in zip(mine, theirs)]
    # the record is stamped a few us inside its annotation; a thread
    # switched out between the two can add to the odd one, not to the median
    off = np.abs(np.asarray(offs))
    assert np.median(off[:, 0]) < 50_000 and np.median(off[:, 1]) < 50_000
