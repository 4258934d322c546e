"""The cold path's host-fetch program (``TieredFeatureStore._host_fetch``):
built once per placement snapshot and traced once per id-vector length,
exact rows from HOST and DISK, each snapshot served by its own program,
and the tracing context handed to the callback per call."""
import os
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import (TieredFeatureStore, TopologySpec, compute_fap,
                        quiver_placement)
from repro.core.placement import TIER_DISK, TIER_HOST
from repro.graph import power_law_graph

N, D = 400, 6


@pytest.fixture(autouse=True)
def tracing_off():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()


@pytest.fixture
def store():
    """80 rows in HBM, 120 in HOST, 200 on DISK; a fresh store (and so a
    fresh program cache) for every test."""
    g = power_law_graph(N, 6.0, seed=0)
    feats = np.random.default_rng(0).normal(size=(N, D)).astype(np.float32)
    topo = TopologySpec(num_pods=1, devices_per_pod=1, rows_per_device=80,
                        rows_host=120, hot_replicate_fraction=0.2)
    st = TieredFeatureStore.build(
        feats, quiver_placement(compute_fap(g, (4, 3)), topo))
    st.feats = feats
    return st


def _tables(store):
    _, _, host, disk, tier_t, slot_t, _ = store._snapshot()
    return host, disk, np.asarray(tier_t), np.asarray(slot_t)


def _args(tier, slot, ids):
    return (jnp.asarray(tier[ids].astype(np.int32)),
            jnp.asarray(slot[ids].astype(np.int32)))


def _cold_mix(tier, k):
    """``k`` HOST ids followed by ``k`` DISK ids."""
    return np.concatenate([np.flatnonzero(tier == TIER_HOST)[:k],
                           np.flatnonzero(tier == TIER_DISK)[:k]])


def _traces(store):
    return store.snapshot_stats()["host_fetch_traces"]


def test_one_trace_per_snapshot_and_id_length(store):
    _, _, tier, slot = _tables(store)
    host_ids = np.flatnonzero(tier == TIER_HOST)
    store.reset_stats()
    tracing.enable()
    for k in range(6):                     # same length, other ids
        np.asarray(store._host_fetch(*_args(tier, slot,
                                            host_ids[k:k + 8])))
    assert _traces(store) == 1
    assert tracing.compiles() <= 1
    for k in range(3):
        np.asarray(store._host_fetch(*_args(tier, slot,
                                            host_ids[k:k + 16])))
    assert _traces(store) == 2
    assert tracing.compiles() <= 2
    # tracing off: token 0, nothing left behind for a callback to pop
    tracing.disable()
    np.asarray(store._host_fetch(*_args(tier, slot, host_ids[:8])))
    assert _traces(store) == 2 and store._fetch_ctx == {}
    assert store.snapshot_stats()["host_fetches"] == 0   # _resolve_cold's


def test_rows_are_a_plain_gather_of_host_and_disk(store):
    host, disk, tier, slot = _tables(store)
    ids = _cold_mix(tier, 12)
    np.random.default_rng(1).shuffle(ids)
    got = np.asarray(store._host_fetch(*_args(tier, slot, ids)))
    want = np.where((tier[ids] == TIER_HOST)[:, None],
                    np.asarray(host)[np.minimum(slot[ids], len(host) - 1)],
                    disk[np.minimum(slot[ids], len(disk) - 1)])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, store.feats[ids])
    # through every lookup route, with a third of the cold ids staged
    stage_ids = ids[::3]
    stage_slot = np.full(N, -1, np.int32)
    stage_slot[stage_ids] = np.arange(stage_ids.size, dtype=np.int32)
    store.publish_stage(stage_slot, jnp.asarray(store.feats[stage_ids]))
    store.reset_stats()
    mixed = np.concatenate([ids, np.flatnonzero(tier < TIER_HOST)[:5],
                            [-1]]).astype(np.int32)
    want = np.where((mixed >= 0)[:, None], store.feats[mixed], 0.0)
    np.testing.assert_array_equal(np.asarray(store.lookup(mixed)), want)
    np.testing.assert_array_equal(
        np.asarray(store.lookup_hops([mixed[:9], mixed[9:]])[1]), want[9:])
    stats = store.snapshot_stats()
    assert stats["prefetch_hits"] == 2 * stage_ids.size
    assert stats["host_fetches"] == 2
    # lookup and lookup_hops dedup to the same length: one program
    assert stats["host_fetch_traces"] == 1


def test_each_snapshot_fetches_through_its_own_program(store):
    old_host, old_disk, _, _ = _tables(store)
    a, b = np.flatnonzero(np.asarray(store.plan.tier) == TIER_HOST)[:2]
    d = int(np.flatnonzero(np.asarray(store.plan.tier) == TIER_DISK)[0])
    h = int(np.flatnonzero(np.asarray(store.plan.tier) == TIER_HOST)[5])
    ids = np.array([a, b, d, h], np.int32)
    np.testing.assert_array_equal(np.asarray(store.lookup(ids)),
                                  store.feats[ids])
    store.reset_stats()
    # a and b exchange HOST slots; d is promoted into h's HOST slot
    assert store.swap_assignments([(int(a), int(b)), (d, h)]) == 4
    host, disk, tier, slot = _tables(store)
    assert host is not old_host and disk is not old_disk
    assert tier[d] == TIER_HOST and tier[h] == TIER_DISK
    np.testing.assert_array_equal(np.asarray(store.lookup(ids)),
                                  store.feats[ids])
    assert _traces(store) == 1                   # the new snapshot's build
    # a fetch still holding the old snapshot reads the old arrays, even
    # though the cached program is now the new snapshot's
    args = _args(tier, slot, ids)
    got = np.asarray(store._host_fetch(*args, old_host, old_disk))
    want = np.where((tier[ids] == TIER_HOST)[:, None],
                    np.asarray(old_host)[slot[ids]],
                    old_disk[np.minimum(slot[ids], len(old_disk) - 1)])
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got, store.feats[ids])
    np.testing.assert_array_equal(
        np.asarray(store._host_fetch(*args)), store.feats[ids])


def test_concurrent_fetches_keep_their_own_tracing_context(store):
    """More threads than cores, switching often: each callback carries
    its own fetch's request and parent."""
    _, _, tier, slot = _tables(store)
    ids = _cold_mix(tier, 8)
    np.asarray(store._host_fetch(*_args(tier, slot, ids)))   # compile
    n_threads, n_fetches = min(os.cpu_count() or 4, 32) + 2, 10
    start = threading.Barrier(n_threads)
    errors = []

    def worker(req):
        try:
            start.wait(timeout=30)
            for k in range(n_fetches):
                with tracing.span("store.cold", req=req):
                    np.asarray(store._host_fetch(*_args(
                        tier, slot, np.roll(ids, k + req))))
        except Exception as e:          # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(1, n_threads + 1)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    tracing.enable()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
        tracing.disable()
    assert not errors and not any(t.is_alive() for t in threads)
    recs = tracing.drain()
    by_id = {r.span_id: r for r in recs}
    cbs = [r for r in recs if r.name == "store.callback"]
    assert sorted(r.req for r in cbs) == sorted(
        list(range(1, n_threads + 1)) * n_fetches)
    for cb in cbs:
        fetch = by_id[cb.parent]
        assert fetch.name == "store.host_fetch" and fetch.req == cb.req
        assert by_id[fetch.parent].req == cb.req
    assert len({cb.parent for cb in cbs}) == len(cbs)   # one a fetch
    assert store._fetch_ctx == {}


def test_the_token_carries_the_dispatching_context(store):
    """On the CPU the callback runs on the dispatching thread and would
    inherit its context anyway, so each half of the hand-over is checked
    on its own: ``_host_fetch`` files ``tracing.current()`` under the token
    it passes, and the callback takes its context from the token."""
    _, _, tier, slot = _tables(store)
    args = _args(tier, slot, _cold_mix(tier, 4))
    np.asarray(store._host_fetch(*args))            # builds the program
    host, disk, prog = store._fetch_prog
    seen = []

    def spy(tier, slot, token):
        seen.append((int(token), store._fetch_ctx.get(int(token))))
        return prog(tier, slot, token)

    store._fetch_prog = (host, disk, spy)
    tracing.enable()
    with tracing.span("store.cold", req=3):
        np.asarray(store._host_fetch(*args))
        store._fetch_ctx[77] = (9, 12345)
        np.asarray(prog(*args, np.int32(77)))
    tracing.disable()
    recs = tracing.drain()
    fetch = next(r for r in recs if r.name == "store.host_fetch")
    [(token, ctx)] = seen
    assert token > 0 and ctx == (3, fetch.span_id)
    assert [(r.req, r.parent) for r in recs if r.name == "store.callback"] \
        == [(3, fetch.span_id), (9, 12345)]
    assert store._fetch_ctx == {}
