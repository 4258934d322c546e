"""``correct`` comes out false for the control and for each fault the
served path can have, with the rest of a run driven as the benchmark
drives it (the look for a chip skipped, a test-only size on the CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtest_util import last_json, make_root, run_main


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("checkout")))


def _serve(root, capsys) -> dict:
    rc = run_main(root, ["--workload", "tiny.open", "--seed", "17",
                         "--seconds", "1.5", "--trace", "0"])
    assert rc == 0
    return last_json(capsys.readouterr()[0])


def _bf16(x):
    return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)


def control(monkeypatch):
    """The reference in the program's place one precision down: rows
    collected in bfloat16 and the model computed in bfloat16."""
    from bench.lib import model, reference
    from repro.core import TieredFeatureStore

    orig = TieredFeatureStore.lookup_hops
    monkeypatch.setattr(TieredFeatureStore, "lookup_hops",
                        lambda self, hops, **kw: [_bf16(r) for r in
                                                  orig(self, hops, **kw)])

    def served(self, hop_feats, hop_ids, deep_agg=None):
        out = reference.sage_reference(self.params, hop_feats, hop_ids,
                                       self.fanouts, dtype=jnp.bfloat16,
                                       precision="default")
        return out.astype(jnp.float32)
    monkeypatch.setattr(model.Served, "__call__", served)


def answer_altered(monkeypatch):
    from repro.serving import executors

    for cls in (executors.HostExecutor, executors.DeviceExecutor):
        orig = cls.process
        monkeypatch.setattr(cls, "process", lambda self, s, _o=orig:
                            _o(self, s).at[0, 0].add(0.05))


def half_the_batch_left_out(monkeypatch):
    from repro.serving import executors

    for cls in (executors.HostExecutor, executors.DeviceExecutor):
        orig = cls.process

        def process(self, seeds, _o=orig):
            out = _o(self, seeds)
            keep = (jnp.arange(out.shape[0]) < (out.shape[0] + 1) // 2)
            return jnp.where(keep[:, None], out, 0.0)
        monkeypatch.setattr(cls, "process", process)


def neighbour_altered(monkeypatch):
    from repro.serving import executors

    def shift(hops):
        h = np.asarray(hops[1]).copy()
        i = int(np.argmax(h >= 0))
        h[i] = (h[i] + 1) % 3000
        return [hops[0], jnp.asarray(h), *hops[2:]]

    dev, host = executors.device_sample, executors.host_sample_dense
    monkeypatch.setattr(executors, "device_sample",
                        lambda *a, **k: shift(dev(*a, **k)))
    monkeypatch.setattr(executors, "host_sample_dense",
                        lambda *a, **k: shift(host(*a, **k)))


def row_altered(monkeypatch):
    from repro.core import TieredFeatureStore

    orig = TieredFeatureStore.lookup_hops

    def lookup_hops(self, hops, **kw):
        rows = orig(self, hops, **kw)
        return [rows[0], rows[1].at[0, 0].add(1.0), *rows[2:]]
    monkeypatch.setattr(TieredFeatureStore, "lookup_hops", lookup_hops)


@pytest.mark.parametrize("fault, caught_by", [
    (control, "row_mismatches"),
    (answer_altered, "output_gap"),
    (half_the_batch_left_out, "output_gap"),
    (neighbour_altered, "sample_bad_slots"),
    (row_altered, "row_mismatches"),
])
def test_correct_is_false(root, capsys, monkeypatch, fault, caught_by):
    fault(monkeypatch)
    res = _serve(root, capsys)
    assert res["correct"] is False
    value, op, limit = res["checks"][caught_by]
    assert value > limit


def test_the_control_fails_the_output_gap_too(root, capsys, monkeypatch):
    control(monkeypatch)
    value, _, limit = _serve(root, capsys)["checks"]["output_gap"]
    assert value > 3 * limit


def test_a_sound_run_is_correct(root, capsys):
    res = _serve(root, capsys)
    assert res["correct"] is True
    assert jax.devices()[0].platform == "cpu"
