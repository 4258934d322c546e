"""quiverlint (PR 7): positive + negative fixtures for every rule, the
suppression and baseline mechanics, the full-repo zero-findings gate, and
behavioral regression tests for the genuine bugs the first full-repo run
surfaced (torn snapshot reads, stats/metrics published outside their
locks)."""
import json
import sys
import textwrap
import threading
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

from quiverlint import driver, repo_config  # noqa: E402
from quiverlint.driver import SourceFile  # noqa: E402


# ---------------------------------------------------------------------------
# Harness: lint a fixture snippet with a minimal config
# ---------------------------------------------------------------------------
def lint(tmp_path, source, passes, *, configure=None, name="mod.py",
         baseline=None, extra_files=()):
    cfg = repo_config.Config(root=tmp_path)
    if configure:
        configure(cfg)
    paths = [(name, source), *extra_files]
    files = []
    for rel, text in paths:
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
        if rel.endswith(".py"):  # docs files are read from disk, not parsed
            files.append(SourceFile.load(p, tmp_path))
    return driver.run(cfg, files,
                      {n: repo_config.PASSES[n] for n in passes},
                      baseline_path=baseline)


def rules(result):
    return [f.rule for f in result.findings]


LOCK_GUARD = {"C": {"x": "_lock"}}


def lock_cfg(cfg):
    cfg.guarded_fields = LOCK_GUARD


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------
class TestLockDiscipline:
    def test_flags_unguarded_access(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    return self.x
        """, ["lock"], configure=lock_cfg)
        assert rules(res) == ["lock-discipline"]
        assert res.findings[0].symbol == "C.read"
        assert "_lock" in res.findings[0].message

    def test_clean_inside_with_lock(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    with self._lock:
                        return self.x
        """, ["lock"], configure=lock_cfg)
        assert res.findings == []

    def test_wrong_lock_still_flags(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    with self._other:
                        return self.x
        """, ["lock"], configure=lock_cfg)
        assert rules(res) == ["lock-discipline"]

    def test_init_and_exempt_methods_skipped(self, tmp_path):
        def cfg(c):
            c.guarded_fields = LOCK_GUARD
            c.lock_exempt_methods = {"C": {"publish"}}
        res = lint(tmp_path, """
            class C:
                def __init__(self):
                    self.x = 0
                def publish(self):
                    self.x = 1
        """, ["lock"], configure=cfg)
        assert res.findings == []

    def test_nested_function_does_not_inherit_lock(self, tmp_path):
        # a closure may run after the lock is released (executor callback)
        res = lint(tmp_path, """
            class C:
                def read(self):
                    with self._lock:
                        def cb():
                            return self.x
                        return cb
        """, ["lock"], configure=lock_cfg)
        assert rules(res) == ["lock-discipline"]

    def test_wait_for_predicate_counts_as_held(self, tmp_path):
        def cfg(c):
            c.guarded_fields = {"C": {"x": "_acct"}}
        res = lint(tmp_path, """
            class C:
                def drain(self):
                    with self._acct:
                        self._acct.wait_for(lambda: self.x == 0)
        """, ["lock"], configure=cfg)
        assert res.findings == []

    def test_suppression_with_reason(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    return self.x  # quiverlint: disable=lock-discipline atomic ref read
        """, ["lock"], configure=lock_cfg)
        assert res.findings == []
        assert len(res.suppressed) == 1

    def test_suppression_without_reason_is_a_finding(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    return self.x  # quiverlint: disable=lock-discipline
        """, ["lock"], configure=lock_cfg)
        assert sorted(rules(res)) == ["bad-suppression", "lock-discipline"]

    def test_own_line_suppression_covers_next_line(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    # quiverlint: disable=lock-discipline snapshot not needed here
                    return self.x
        """, ["lock"], configure=lock_cfg)
        assert res.findings == []
        assert len(res.suppressed) == 1

    def test_suppression_for_other_rule_does_not_apply(self, tmp_path):
        res = lint(tmp_path, """
            class C:
                def read(self):
                    return self.x  # quiverlint: disable=trace-safety wrong rule
        """, ["lock"], configure=lock_cfg)
        assert rules(res) == ["lock-discipline"]


# ---------------------------------------------------------------------------
# trace-safety
# ---------------------------------------------------------------------------
class TestTraceSafety:
    def test_flags_branch_coercion_numpy_and_mask(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                if x > 0:
                    x = x + 1
                y = float(x)
                z = np.maximum(x, 0)
                m = x > 0
                w = x[m]
                s = x + 2
                return s.item()
        """, ["trace"])
        msgs = " | ".join(f.message for f in res.findings)
        assert len(res.findings) == 5
        assert "control flow" in msgs and "float()" in msgs
        assert "numpy" in msgs and "boolean-mask" in msgs
        assert ".item()" in msgs

    def test_clean_static_and_shape_idioms(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            import jax.numpy as jnp
            import numpy as np
            from functools import partial

            @partial(jax.jit, static_argnames=("mode", "fanouts"))
            def f(x, w=None, *, mode="sum", fanouts=(4, 3)):
                if mode == "mean":          # static_argnames: not traced
                    x = x / 2
                if w is not None:           # identity test never concretizes
                    x = x * w
                n = int(x.shape[0])         # shapes are static under jit
                k = float(fanouts[-1])
                pad = np.zeros((4,))        # numpy on non-traced values
                return jnp.minimum(x, k) + n + jnp.asarray(pad)
        """, ["trace"])
        assert res.findings == []

    def test_reaches_helpers_called_from_jitted_body(self, tmp_path):
        res = lint(tmp_path, """
            import jax

            def helper(y, fanout: int):
                if fanout > 2:              # scalar annotation: static
                    y = y * 2
                return int(y)               # traced! flagged in the helper

            @jax.jit
            def f(x):
                return helper(x, 4)
        """, ["trace"])
        assert rules(res) == ["trace-safety"]
        assert res.findings[0].symbol == "helper"

    def test_pallas_kernel_via_partial_binding(self, tmp_path):
        res = lint(tmp_path, """
            import functools
            from jax.experimental import pallas as pl

            def _kernel(x_ref, o_ref, *, rows: int):
                o_ref[...] = float(x_ref[...])

            def call(x, block_rows: int = 8):
                kernel = functools.partial(_kernel, rows=block_rows)
                return pl.pallas_call(kernel, out_shape=None)(x)
        """, ["trace"])
        assert rules(res) == ["trace-safety"]
        assert "_kernel" in res.findings[0].symbol

    def test_io_callback_host_body_excluded(self, tmp_path):
        res = lint(tmp_path, """
            import jax
            import numpy as np
            from jax.experimental import io_callback

            @jax.jit
            def f(x):
                def cb(x_np):
                    return np.asarray(x_np) * 2   # host code: fine
                return io_callback(cb, x, x)
        """, ["trace"])
        assert res.findings == []

    def test_shard_map_body_checked(self, tmp_path):
        res = lint(tmp_path, """
            import jax

            def body(block):
                while block.sum() > 0:
                    block = block - 1
                return block

            def run(x, mesh):
                return jax.shard_map(body, mesh=mesh, in_specs=None,
                                     out_specs=None)(x)
        """, ["trace"])
        assert rules(res) == ["trace-safety"]
        assert res.findings[0].symbol == "body"


# ---------------------------------------------------------------------------
# callback-budget
# ---------------------------------------------------------------------------
CB_STORE_OK = """
    from jax.experimental import io_callback

    class Store:
        def lookup(self, ids):
            return self._resolve(ids)
        def _resolve(self, ids):
            return self._host_fetch(ids)
        def _host_fetch(self, ids):
            return io_callback(lambda x: x, None, ids)
"""

CB_STORE_BAD = """
    from jax.experimental import io_callback

    class Store:
        def lookup(self, ids):
            return self._resolve(ids)
        def _resolve(self, ids):
            return io_callback(lambda x: x, None, ids)
        def _host_fetch(self, ids):
            return io_callback(lambda x: x, None, ids)
"""


def cb_cfg(c):
    c.hot_path_roots = frozenset({"Store.lookup"})
    c.callback_gateways = frozenset({"Store._host_fetch"})
    c.fetch_gateways = frozenset()
    c.restricted_roots = {}


def cb_cfg_sharded(c):
    # the sharded shape: a host-data fetch gateway (plain numpy, never a
    # callback) plus a root forbidden from reaching the tiered gateway
    cb_cfg(c)
    c.fetch_gateways = frozenset({"Store.read_cold_rows"})
    c.restricted_roots = {"Sharded.lookup": ("Store._host_fetch",)}


class TestCallbackBudget:
    def test_gateway_only_path_is_clean(self, tmp_path):
        res = lint(tmp_path, CB_STORE_OK, ["callback"], configure=cb_cfg)
        assert res.findings == []

    def test_direct_callback_outside_gateway_flagged_with_chain(
            self, tmp_path):
        res = lint(tmp_path, CB_STORE_BAD, ["callback"], configure=cb_cfg)
        assert rules(res) == ["callback-budget"]
        msg = res.findings[0].message
        assert "Store.lookup -> Store._resolve" in msg

    def test_callback_hidden_behind_partial_still_caught(self, tmp_path):
        # broad reference-based edges: storing the method is enough
        res = lint(tmp_path, """
            import functools
            from jax.experimental import io_callback

            class Store:
                def lookup(self, ids):
                    fn = functools.partial(self._fetch_now, ids)
                    return fn()
                def _fetch_now(self, ids):
                    return io_callback(lambda x: x, None, ids)
                def _host_fetch(self, ids):
                    return io_callback(lambda x: x, None, ids)
        """, ["callback"], configure=cb_cfg)
        assert rules(res) == ["callback-budget"]

    def test_missing_root_is_config_drift(self, tmp_path):
        res = lint(tmp_path, """
            class Store:
                def renamed_lookup(self, ids):
                    return ids
        """, ["callback"], configure=cb_cfg)
        assert any("not found" in f.message for f in res.findings)

    def test_vacuous_gateway_flagged(self, tmp_path):
        res = lint(tmp_path, """
            class Store:
                def lookup(self, ids):
                    return self._host_fetch(ids)
                def _host_fetch(self, ids):
                    return ids      # no io_callback: proof is vacuous
        """, ["callback"], configure=cb_cfg)
        assert any("vacuous" in f.message for f in res.findings)

    def test_fetch_gateway_clean_and_stops_restricted_walk(self, tmp_path):
        # a restricted root may *call* the fetch gateway — the walk stops
        # there, so the forbidden qualname behind it is never reached
        res = lint(tmp_path, """
            from jax.experimental import io_callback

            class Store:
                def lookup(self, ids):
                    return self._host_fetch(ids)
                def _host_fetch(self, ids):
                    return io_callback(lambda x: x, None, ids)
                def read_cold_rows(self, ids):
                    return ids          # plain numpy, no callback

            class Sharded:
                def lookup(self, ids):
                    return self.read_cold_rows(ids)
                def read_cold_rows(self, ids):
                    return ids
        """, ["callback"], configure=cb_cfg_sharded)
        assert res.findings == []

    def test_fetch_gateway_with_direct_callback_flagged(self, tmp_path):
        res = lint(tmp_path, """
            from jax.experimental import io_callback

            class Store:
                def lookup(self, ids):
                    return self._host_fetch(ids)
                def _host_fetch(self, ids):
                    return io_callback(lambda x: x, None, ids)
                def read_cold_rows(self, ids):
                    return io_callback(lambda x: x, None, ids)

            class Sharded:
                def lookup(self, ids):
                    return ids
        """, ["callback"], configure=cb_cfg_sharded)
        assert rules(res) == ["callback-budget"]
        assert any("direct io_callback" in f.message for f in res.findings)

    def test_restricted_root_reaching_forbidden_flagged(self, tmp_path):
        res = lint(tmp_path, """
            from jax.experimental import io_callback

            class Store:
                def lookup(self, ids):
                    return self._host_fetch(ids)
                def _host_fetch(self, ids):
                    return io_callback(lambda x: x, None, ids)
                def read_cold_rows(self, ids):
                    return ids

            class Sharded:
                def lookup(self, ids):
                    return self._merge(ids)
                def _merge(self, ids):
                    return Store._host_fetch(self, ids)
        """, ["callback"], configure=cb_cfg_sharded)
        assert rules(res) == ["callback-budget"]
        msg = res.findings[0].message
        assert "Sharded.lookup" in msg and "_host_fetch" in msg

    def test_missing_fetch_gateway_is_config_drift(self, tmp_path):
        res = lint(tmp_path, """
            from jax.experimental import io_callback

            class Store:
                def lookup(self, ids):
                    return self._host_fetch(ids)
                def _host_fetch(self, ids):
                    return io_callback(lambda x: x, None, ids)

            class Sharded:
                def lookup(self, ids):
                    return ids
        """, ["callback"], configure=cb_cfg_sharded)
        assert any("fetch gateway" in f.message and "not found" in f.message
                   for f in res.findings)


# ---------------------------------------------------------------------------
# schema-sync
# ---------------------------------------------------------------------------
SCHEMA_DOC = """
    stats: `a_hits` `a_misses`
    <!-- quiverlint:stats-schema -->
    | `a_hits` | hits |
    | `a_misses` | misses |
    <!-- /quiverlint:stats-schema -->
"""


def schema_cfg(c):
    c.schema = repo_config.SchemaSpec(
        schema_file="store.py", schema_const="STATS_SCHEMA",
        store_class="Store", cache_class="Cache",
        stats_classes=(("store.py", "Cache"),),
        aux_schemas=(),
        marker_doc="docs/invariants.md")


class TestSchemaSync:
    def run_schema(self, tmp_path, source, doc=SCHEMA_DOC):
        return lint(tmp_path, source, ["schema"], configure=schema_cfg,
                    name="store.py",
                    extra_files=[("docs/invariants.md", doc)])

    CLEAN = """
        STATS_SCHEMA = ("a_hits", "a_misses")

        class Store:
            def hit(self):
                self._count(a_hits=1)
            def miss(self):
                self._count(a_misses=1)

        class Cache:
            def __init__(self):
                self.stats = {"hits": 0, "misses": 0}
            def touch(self):
                self.stats["hits"] += 1
                self.stats["misses"] += 1
    """

    def test_clean_schema(self, tmp_path):
        res = self.run_schema(tmp_path, self.CLEAN)
        assert res.findings == []

    def test_unknown_count_key_flagged(self, tmp_path):
        res = self.run_schema(tmp_path, self.CLEAN.replace(
            "self._count(a_hits=1)", "self._count(b_hits=1)"))
        msgs = [f.message for f in res.findings]
        assert any("`b_hits` incremented but absent" in m for m in msgs)
        assert any("`a_hits` is never incremented" in m for m in msgs)

    def test_undeclared_class_stats_key_flagged(self, tmp_path):
        res = self.run_schema(tmp_path, self.CLEAN.replace(
            'self.stats["hits"] += 1', 'self.stats["hitz"] += 1'))
        msgs = [f.message for f in res.findings]
        assert any("'hitz'" in m and "not declared" in m for m in msgs)
        assert any("`hits` is never read" in m for m in msgs)

    def test_cache_mirror_checked(self, tmp_path):
        src = self.CLEAN.replace('"a_hits", "a_misses"',
                                 '"a_hits", "a_misses", "cache_evictions"')
        src = src.replace("self._count(a_misses=1)",
                          "self._count(a_misses=1, cache_evictions=1)")
        res = self.run_schema(tmp_path, src, doc=SCHEMA_DOC.replace(
            "| `a_misses` | misses |",
            "| `a_misses` | misses |\n    | `cache_evictions` | ev |"))
        msgs = [f.message for f in res.findings]
        assert any("mirrors no `evictions` counter" in m for m in msgs)

    def test_docs_table_out_of_sync_flagged(self, tmp_path):
        res = self.run_schema(tmp_path, self.CLEAN, doc="""
            stats: `a_hits` `a_misses`
            <!-- quiverlint:stats-schema -->
            | `a_hits` | hits |
            | `stale_key` | gone |
            <!-- /quiverlint:stats-schema -->
        """)
        msgs = [f.message for f in res.findings]
        assert any("`a_misses` missing from" in m for m in msgs)
        assert any("`stale_key` is not in STATS_SCHEMA" in m for m in msgs)

    AUX_DOC = SCHEMA_DOC + """
    <!-- quiverlint:aux-x -->
    | `hits` | h |
    | `misses` | m |
    <!-- /quiverlint:aux-x -->
    """

    def aux_cfg(self, c):
        schema_cfg(c)
        c.schema.aux_schemas = (("store.py", "AUX_SCHEMA", "Cache",
                                 "aux-x"),)

    def test_aux_schema_clean(self, tmp_path):
        src = self.CLEAN + '\n        AUX_SCHEMA = ("hits", "misses")\n'
        res = lint(tmp_path, src, ["schema"], configure=self.aux_cfg,
                   name="store.py",
                   extra_files=[("docs/invariants.md", self.AUX_DOC)])
        assert res.findings == []

    def test_aux_schema_drift_flagged_everywhere(self, tmp_path):
        """One drifted aux constant fires on all three surfaces: the stats
        declaration, the doc table, and a missing constant entirely."""
        src = self.CLEAN + '\n        AUX_SCHEMA = ("hits", "evictions")\n'
        res = lint(tmp_path, src, ["schema"], configure=self.aux_cfg,
                   name="store.py",
                   extra_files=[("docs/invariants.md", self.AUX_DOC)])
        msgs = [f.message for f in res.findings]
        assert any("key `evictions` missing from Cache's stats declaration"
                   in m for m in msgs)
        assert any("stats key `misses` is absent from `AUX_SCHEMA`" in m
                   for m in msgs)
        assert any("key `evictions` missing from the aux-x table" in m
                   for m in msgs)
        assert any("documented key `misses` is not in `AUX_SCHEMA`" in m
                   for m in msgs)
        # constant deleted outright -> flagged, not silently skipped
        res = lint(tmp_path / "gone", self.CLEAN, ["schema"],
                   configure=self.aux_cfg, name="store.py",
                   extra_files=[("docs/invariants.md", self.AUX_DOC)])
        assert any("aux schema constant `AUX_SCHEMA` not found" in
                   f.message for f in res.findings)

    def test_aux_schema_missing_marker_block_flagged(self, tmp_path):
        src = self.CLEAN + '\n        AUX_SCHEMA = ("hits", "misses")\n'
        res = lint(tmp_path, src, ["schema"], configure=self.aux_cfg,
                   name="store.py",
                   extra_files=[("docs/invariants.md", SCHEMA_DOC)])
        assert any("no `<!-- quiverlint:aux-x -->` block found" in
                   f.message for f in res.findings)


# ---------------------------------------------------------------------------
# docs pass (folded-in check_docs)
# ---------------------------------------------------------------------------
class TestDocsPass:
    def test_broken_link_and_missing_docstring(self, tmp_path):
        def cfg(c):
            c.docs = repo_config.DocsSpec(api={"api.py": ["Thing"]})
        res = lint(tmp_path, """
            class Thing:
                def run(self):
                    return 1
        """, ["docs"], configure=cfg, name="api.py",
            extra_files=[("README.md", "[dead](missing.md)\n")])
        got = sorted(rules(res))
        assert got == ["docs-docstring", "docs-docstring", "docs-link"]

    def test_clean_docs(self, tmp_path):
        def cfg(c):
            c.docs = repo_config.DocsSpec(api={"api.py": ["Thing.run"]})
        res = lint(tmp_path, '''
            class Thing:
                """A thing."""
                def run(self):
                    """Runs."""
        ''', ["docs"], configure=cfg, name="api.py",
            extra_files=[("README.md", "[ok](api.py) [web](https://x)\n")])
        assert res.findings == []


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------
class TestBaseline:
    SRC = """
        class C:
            def read(self):
                return self.x
    """
    FIXED = """
        class C:
            def read(self):
                with self._lock:
                    return self.x
    """

    def test_round_trip_then_stale(self, tmp_path):
        baseline = tmp_path / "baseline.json"
        res = lint(tmp_path, self.SRC, ["lock"], configure=lock_cfg,
                   baseline=baseline)
        assert rules(res) == ["lock-discipline"]
        driver.write_baseline(baseline, res.findings)
        assert json.loads(baseline.read_text())["findings"]

        # baselined: finding demoted, run is ok
        res = lint(tmp_path, self.SRC, ["lock"], configure=lock_cfg,
                   baseline=baseline)
        assert res.ok and res.findings == [] and len(res.baselined) == 1

        # the line-independent key survives code shifting down the file
        res = lint(tmp_path, "\n\n" + textwrap.dedent(self.SRC), ["lock"],
                   configure=lock_cfg, baseline=baseline)
        assert res.ok and len(res.baselined) == 1

        # fixed code -> the baseline entry goes stale and fails the run
        res = lint(tmp_path, self.FIXED, ["lock"], configure=lock_cfg,
                   baseline=baseline)
        assert not res.ok and res.findings == []
        assert len(res.stale_baseline) == 1


# ---------------------------------------------------------------------------
# the full-repo gate: the tool's own CI contract
# ---------------------------------------------------------------------------
class TestFullRepo:
    def test_repo_is_clean_via_main(self, capsys):
        rc = driver.main(["--root", str(REPO), "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0, out["findings"]
        assert out["ok"] and out["findings"] == []
        assert out["stale_baseline"] == []
        assert set(out["passes"]) == set(repo_config.PASSES)

    def test_callback_budget_proves_gateway_property(self):
        """The zero-callback property: io_callback appears in exactly one
        function of src/repro, and that function is the registered
        gateway reachable from the hot-path roots."""
        from quiverlint import callback_budget, callgraph
        cfg = repo_config.build(REPO)
        files = driver.collect_files(REPO, ["src/repro/**/*.py"])
        index = callgraph.Index(files)
        direct = callback_budget._direct_callers(cfg, index)
        assert sorted(r.split("::")[1] for r in direct) == \
            ["TieredFeatureStore._host_fetch"]
        roots = [f for q in cfg.hot_path_roots
                 for f in index.by_qualname.get(q, [])]
        reached = callgraph.reachable_broad(
            index, roots, stop=set(cfg.callback_gateways))
        assert any(r.endswith("TieredFeatureStore._host_fetch")
                   for r in reached), "gateway unreachable from hot path"


# ---------------------------------------------------------------------------
# regression tests for the true positives the first full-repo run found
# ---------------------------------------------------------------------------
class LockProbe:
    """threading.Lock wrapper recording acquisitions and held state."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acquired = 0
        self.held = False

    def __enter__(self):
        self._lock.acquire()
        self.acquired += 1
        self.held = True
        return self

    def __exit__(self, *exc):
        self.held = False
        self._lock.release()


class TestLockRegressions:
    def test_cache_report_snapshots_under_lock(self):
        """GPUFeatureCache.report read `capacity` outside _lock; a
        concurrent resize could pair old capacity with new stats."""
        from repro.core import GPUFeatureCache
        cache = GPUFeatureCache(num_nodes=16, capacity=4, feat_dim=2)
        probe = LockProbe()
        cache._lock = probe
        rep = cache.report()
        assert probe.acquired >= 1
        assert rep["capacity"] == 4 and rep["resident"] == 0

    def test_engine_reset_publishes_metrics_under_lock(self):
        """ServingEngine._reset assigned self._metrics without _lock,
        racing submit_batch's bind of the current run's metrics."""
        import time

        from repro.serving.engine import ServingEngine
        eng = ServingEngine.__new__(ServingEngine)
        probe = LockProbe()
        eng._lock = probe
        eng._metrics = None
        eng.clock = time.monotonic    # normally injected by __init__
        metrics = eng._reset()
        assert probe.acquired == 1
        assert eng._metrics is metrics and metrics.started > 0

    def test_adaptive_report_snapshots_stats_under_lock(self):
        """AdaptiveController.report iterated self.stats['last_drift']
        unlocked while refit_curves mutates it -> possible
        dictionary-changed-size-during-iteration."""
        from repro.serving.adaptive import AdaptiveController

        class _Sketch:
            total_observed = 7

        ctl = AdaptiveController.__new__(AdaptiveController)
        probe = LockProbe()
        ctl._lock = probe
        ctl.sketch = _Sketch()
        ctl.stats = {"steps": 3, "last_drift": {"host": 0.5}}
        rep = ctl.report()
        assert probe.acquired == 1
        assert rep["steps"] == 3 and rep["last_drift"] == {"host": 0.5}
        assert rep["seeds_observed"] == 7

    def test_refit_writes_last_drift_under_lock(self):
        """refit_curves wrote stats['last_drift'][key] outside _lock."""
        import collections

        from repro.core.serving import DEFAULT_MODEL
        from repro.serving.adaptive import AdaptiveConfig, AdaptiveController
        from repro.serving.router import LatencyCurve

        probe = LockProbe()

        class GuardedDict(dict):
            def __setitem__(self, key, value):
                assert probe.held, \
                    "last_drift written without holding _lock"
                super().__setitem__(key, value)

        class _Router:
            def curve(self, name):
                return LatencyCurve.fit([1, 2, 3, 4], [1, 2, 3, 4], bins=2)

            def update_curve(self, name, curve):
                pass

        ctl = AdaptiveController.__new__(AdaptiveController)
        ctl._lock = probe
        ctl.config = AdaptiveConfig(min_refit_samples=4)
        ctl.routers = {DEFAULT_MODEL: _Router()}
        drift_log = GuardedDict()
        ctl.stats = {"refits": 0, "last_drift": drift_log}
        ctl.samples = {(DEFAULT_MODEL, "host"): collections.deque(
            [(1.0, 5.0), (2.0, 9.0), (3.0, 14.0), (4.0, 20.0)])}
        ctl.refit_curves()
        assert list(drift_log) == ["host"]

    def test_host_fetch_default_args_match_explicit_snapshot(self, tmp_path):
        """_host_fetch's fallback read self.host and self.disk in two
        separate loads (could tear across a migration publish) and sized
        its result from self.hot's dtype; it must behave exactly as if
        handed one coherent snapshot."""
        import jax.numpy as jnp

        from repro.core import (TieredFeatureStore, TopologySpec,
                                compute_fap, quiver_placement)
        from repro.graph import power_law_graph

        n, d = 400, 6
        g = power_law_graph(n, 6.0, seed=0)
        feats = np.random.default_rng(0).normal(size=(n, d)) \
            .astype(np.float32)
        topo = TopologySpec(num_pods=1, devices_per_pod=1,
                            rows_per_device=80, rows_host=120,
                            hot_replicate_fraction=0.2)
        store = TieredFeatureStore.build(
            feats, quiver_placement(compute_fap(g, (4, 3)), topo))
        hot, warm, host, disk, tier_t, slot_t, _ = store._snapshot()
        cold = np.flatnonzero(np.asarray(tier_t) >= 2)[:16]
        tier = jnp.asarray(np.asarray(tier_t)[cold].astype(np.int32))
        slot = jnp.asarray(np.asarray(slot_t)[cold].astype(np.int32))
        via_default = np.asarray(store._host_fetch(tier, slot))
        via_explicit = np.asarray(
            store._host_fetch(tier, slot, host, disk))
        np.testing.assert_array_equal(via_default, via_explicit)
        np.testing.assert_allclose(via_default, feats[cold])

    def test_promote_misses_consistent_under_migration_churn(self):
        """promote_misses read tier_t and slot_t in two separate attribute
        loads — pairing a node's new tier with its old slot across a
        migration publish. Smoke the production shape: publishers
        (swap_assignments / promote_misses) serialized by a step lock as
        the adaptive controller does, lookups concurrent and unserialized
        — every lookup must stay bit-equivalent throughout."""
        import jax.numpy as jnp

        from repro.core import (TieredFeatureStore, TopologySpec,
                                compute_fap, quiver_placement)
        from repro.core.placement import TIER_DISK, TIER_HOST
        from repro.graph import power_law_graph

        n, d = 500, 4
        g = power_law_graph(n, 6.0, seed=1)
        feats = np.random.default_rng(1).normal(size=(n, d)) \
            .astype(np.float32)
        topo = TopologySpec(num_pods=1, devices_per_pod=1,
                            rows_per_device=90, rows_host=140,
                            hot_replicate_fraction=0.2)
        store = TieredFeatureStore.build(
            feats, quiver_placement(compute_fap(g, (4, 3)), topo))
        disk_ids = np.flatnonzero(store.plan.tier == TIER_DISK)
        host_ids = np.flatnonzero(store.plan.tier == TIER_HOST)
        assert disk_ids.size >= 8 and host_ids.size >= 8
        with store._stats_lock:
            store._disk_miss_counts[disk_ids[:8]] = 50

        step_lock = threading.Lock()
        stop = threading.Event()
        errors = []
        probe = np.concatenate([disk_ids[:8], host_ids[:8]])
        probe_ids = jnp.asarray(probe, jnp.int32)

        def reader():
            try:
                while not stop.is_set():
                    got = np.asarray(store.lookup(probe_ids))
                    np.testing.assert_allclose(got, feats[probe])
            except BaseException as exc:  # pragma: no cover
                errors.append(exc)

        def churn():
            try:
                for k in range(6):
                    a = int(host_ids[2 * k]); b = int(host_ids[2 * k + 1])
                    with step_lock:
                        store.swap_assignments([(a, b)])
            except BaseException as exc:  # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=reader),
                   threading.Thread(target=churn)]
        for t in threads:
            t.start()
        moved = 0
        for _ in range(4):
            with step_lock:
                moved += store.promote_misses(budget=2, min_misses=10)
        threads[1].join()
        stop.set()
        threads[0].join()
        assert not errors, errors[0]
        assert moved > 0 and store.promoted_rows == moved
        got = np.asarray(store.lookup(probe_ids))
        np.testing.assert_allclose(got, feats[probe])
