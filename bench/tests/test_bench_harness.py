"""The window driver end to end at a test-only size on the CPU, the
refusal to run off a TPU, and discovery of cells by name."""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

from benchtest_util import ROOT, last_json, make_root, run_main

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SEED = 3_000_000_000_123           # wider than 32 bits, as the driver's are


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("checkout")))


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_window_driver_prints_the_contract_line(root, cell, capsys):
    rc = run_main(root, ["--workload", cell, "--seed", str(SEED),
                         "--seconds", "2", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 0
    res = last_json(out)
    assert CONTRACT_KEYS <= set(res)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        e2e = {m["name"] for m in json.load(f)["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert err.strip().splitlines()[-1].startswith("check ")


def test_traced_run_reports_per_layer_metrics(root, capsys):
    rc = run_main(root, ["--workload", "tiny.open", "--seed", "5",
                         "--seconds", "2", "--trace", "1"])
    res = last_json(capsys.readouterr()[0])
    assert rc == 0 and res["correct"] is True
    got = set(res["metrics"])
    # host-clock and counter readers read something on any backend; the
    # device-trace readers need a TPU plane and stay silent here
    assert {"gen_lag_p95_ms", "admission_wait_p95_ms", "device_route_share",
            "collect_p50_ms", "cold_fetches_per_request"} <= got
    assert not got & {"sample_device_ms_p50", "gather_roofline",
                      "device_idle_share"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


def test_same_seed_same_inputs():
    from bench.lib import traffic as tr
    import numpy as np

    mix = {"loop": "open", "rate_rps": 50,
           "seeds": {"dist": "log_uniform", "min": 1, "max": 128}}
    draw = tr.SeedDraw(np.arange(1000), "out_degree")
    a = tr.open_schedule(mix, 4.0, SEED, draw)
    b = tr.open_schedule(mix, 4.0, SEED, draw)
    c = tr.open_schedule(mix, 4.0, SEED + 1, draw)
    assert np.array_equal(a[0], b[0])
    assert all(np.array_equal(x, y) for x, y in zip(a[1], b[1]))
    # another seed: the same sizes and gaps, in another order
    assert sorted(map(len, a[1])) == sorted(map(len, c[1]))
    n = len(a[1])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = np.round(gaps * 4.0 / gaps.sum(), 9)
    for due in (a[0], c[0]):
        assert np.isin(np.round(np.diff(due), 9), gaps).all()
    assert not np.array_equal(a[0], c[0])
    sizes = np.array([len(s) for s in a[1]])
    assert sizes.min() == 1 and sizes.max() >= 125
    assert 9 <= np.median(sizes) <= 13


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "products.mixed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "TPU" in p.stderr


def test_a_new_cell_is_found_by_name_without_editing_files(tmp_path):
    from bench.lib import cells

    root = make_root(str(tmp_path))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(root, "bench", "**", "*.*"), recursive=True)}
    with open(os.path.join(root, "bench", "configs", "new-cfg.json"),
              "w") as f:
        json.dump({"num_nodes": 7}, f)
    with open(os.path.join(root, "bench", "traffic", "new_mix.json"),
              "w") as f:
        json.dump({"loop": "open"}, f)
    for cell, rate in (("new.cell", 9), ("new.on_old_mix", 3)):
        with open(os.path.join(root, "bench", "cells", f"{cell}.json"),
                  "w") as f:
            json.dump({"rate_rps": rate}, f)
    with open(os.path.join(root, "bench", "metrics", "new_metric.py"),
              "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "new.cell", "config": "new-cfg",
                               "traffic": "new_mix", "chips": 1,
                               "why": "test"})
    # a new configuration on a mix that is already there
    bench["workloads"].append({"name": "new.on_old_mix",
                               "config": "new-cfg", "traffic": "tiny_open",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "seeds_per_s",
                               "workloads": ["new.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = cells.workload("new.cell", root)
    assert cells.config(cell["config"], root)["num_nodes"] == 7
    assert cells.mix(cell, root) == {"loop": "open", "name": "new_mix",
                                     "rate_rps": 9}
    other = cells.workload("new.on_old_mix", root)
    assert cells.config(other["config"], root)["num_nodes"] == 7
    old_mix = cells.mix(other, root)
    assert old_mix["rate_rps"] == 3 and old_mix["seeds"]["max"] == 32
    assert cells.mix(cells.workload("tiny.open", root), root)[
        "rate_rps"] == 40
    assert all(open(p, "rb").read() == b for p, b in before.items())
    names = [m["name"] for m in cells.metrics_for("new.cell", "per_layer",
                                                  root)]
    assert "new_metric" in names and "gen_lag_p95_ms" not in names
    assert "admission_wait_p95_ms" in names        # listed for every cell
    assert cells.reader("new_metric", root)(None) == 42.0


def test_peaks_are_keyed_by_device_kind():
    from bench.lib import peaks

    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
