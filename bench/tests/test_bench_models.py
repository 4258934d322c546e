"""The served model comes from the configuration's ``model`` key: the
GraphSAGE of ``bench/models/graphsage-mean.py`` keeps the FLOP count and
the output of what it replaced, and a model that only adds files (a
test-only stub here) is the one the harness builds, serves, counts and
checks."""
from __future__ import annotations

import glob
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchtest_util import BENCH, FIXTURES, last_json, make_root, run_main
from bench.lib import cells

STUB = '''"""Test-only served model: one linear map of each seed's own row."""
import jax
import jax.numpy as jnp

from bench.lib.model import PRECISIONS, key_of

CALLS = {"make_params": 0, "served": 0, "infer": 0, "reference": 0,
         "flops_per_seed": 0}


def make_params(seed, cfg):
    CALLS["make_params"] += 1
    return {"w": jax.random.normal(key_of(seed, 9), (cfg["feat_dim"], 5))}


def served(params, cfg):
    CALLS["served"] += 1

    def infer(hop_feats, hop_ids, deep_agg=None):
        CALLS["infer"] += 1
        return jnp.dot(hop_feats[0], params["w"])
    return infer


def reference(params, hop_rows, hop_ids, fanouts, *, dtype=jnp.float32,
              precision="highest"):
    CALLS["reference"] += 1
    return jnp.dot(jnp.asarray(hop_rows[0], dtype),
                   jnp.asarray(params["w"], dtype),
                   precision=PRECISIONS[precision])


def flops_per_seed(cfg):
    CALLS["flops_per_seed"] += 1
    return 2 * cfg["feat_dim"] * 5
'''


def test_graphsage_flops_per_seed_of_the_served_configurations():
    sage = cells.model("graphsage-mean")
    assert sage.flops_per_seed(cells.config("products-sage2")) == 884_736
    assert sage.flops_per_seed(cells.config("papers-cut-sage2")) == 1_114_112


def test_graphsage_served_output_matches_its_reference():
    sage = cells.model("graphsage-mean")
    with open(os.path.join(FIXTURES, "tiny-sage2.json")) as f:
        cfg = json.load(f)
    fanouts = tuple(cfg["fanouts"])
    rng = np.random.default_rng(3)
    n = 8
    ids = [np.arange(n)]
    for fan in fanouts:
        ids.append(rng.integers(0, 3000, ids[-1].shape[0] * fan))
    ids[-1][::7] = -1                              # padded slots
    rows = [np.where((i >= 0)[:, None],
                     rng.standard_normal((i.shape[0], cfg["feat_dim"])), 0
                     ).astype(np.float32) for i in ids]
    params = sage.make_params(3_000_000_000_777, cfg)
    out = sage.served(params, cfg)([jnp.asarray(r) for r in rows],
                                   [jnp.asarray(i) for i in ids])
    ref = sage.reference(params, rows, ids, fanouts, dtype=jnp.float32,
                         precision="highest")
    assert out.shape == (n, cfg["hidden"][-1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.fixture
def stub_root(tmp_path):
    root = make_root(str(tmp_path))
    os.makedirs(os.path.join(root, "bench", "models"))
    with open(os.path.join(root, "bench", "models", "stub-linear.py"),
              "w") as f:
        f.write(STUB)
    with open(os.path.join(FIXTURES, "tiny-sage2.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-stub", model="stub-linear")
    with open(os.path.join(root, "bench", "configs", "tiny-stub.json"),
              "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.stub", "config": "tiny-stub",
                               "traffic": "tiny_closed", "chips": 1,
                               "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_a_new_model_needs_only_new_files(stub_root, capsys):
    harness_files = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(BENCH, "**", "*.*"), recursive=True)
        if "__pycache__" not in p}
    rc = run_main(stub_root, ["--workload", "tiny.stub", "--seed", "11",
                              "--seconds", "1.5", "--trace", "0"])
    res = last_json(capsys.readouterr()[0])
    assert rc == 0 and res["correct"] is True, res["checks"]
    stub = cells.model("stub-linear", stub_root)
    assert stub.CALLS["make_params"] == 1 and stub.CALLS["served"] == 1
    assert stub.CALLS["infer"] >= res["attempted"]
    assert stub.CALLS["reference"] >= 1 and stub.CALLS["flops_per_seed"] == 1
    # the stub's weights reached the program: a served answer is its map
    assert res["checks"]["output_gap"][0] < 1e-4
    assert all(open(p, "rb").read() == b for p, b in harness_files.items())


def test_a_model_served_wrong_is_caught(stub_root, capsys, monkeypatch):
    stub = cells.model("stub-linear", stub_root)
    orig = stub.served

    def served(params, cfg):
        infer = orig(params, cfg)
        return lambda f, i, d=None: infer(f, i, d) + 0.01
    monkeypatch.setattr(stub, "served", served)
    rc = run_main(stub_root, ["--workload", "tiny.stub", "--seed", "12",
                              "--seconds", "1.5", "--trace", "0"])
    res = last_json(capsys.readouterr()[0])
    assert rc == 0 and res["correct"] is False
    value, _, limit = res["checks"]["output_gap"]
    assert value > limit
