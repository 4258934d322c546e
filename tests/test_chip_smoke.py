"""``chip_smoke.py``: refuses to report anything without a TPU, its
one-chip phases hold on the CPU at a tiny shape (oracle gathers; the
Mosaic-kernel check is the one part only a chip can answer), and the
compile cache lands where ``use_compile_cache`` says."""
import importlib.util
import os
import subprocess
import sys

import pytest

from tests.conftest import REPO


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.subprocess
def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("fuse_aggregate", [False, True])
def test_serve_phase_checks_hold_at_tiny_shape(monkeypatch, fuse_aggregate):
    cs = _load_chip_smoke()
    from repro import serving
    from repro.launch.serve import build_stack

    monkeypatch.setattr(cs, "MAX_BATCH", 16)
    monkeypatch.setattr(cs, "REQUESTS", 4)
    monkeypatch.setattr(cs, "has_kernel", lambda *a: True)
    # CPU timings may route everything to the host executor; the chip run
    # is where the calibrated router must pick the device
    monkeypatch.setattr(serving.CostModelRouter, "from_curves",
                        lambda *a, **k: serving.StaticScheduler("device"))
    stack = build_stack(nodes=3000, avg_degree=cs.EDGES / cs.NODES,
                        d_feat=cs.D_FEAT, fanouts=cs.FANOUTS, hot_frac=0.25,
                        seed=cs.SEED)
    assert all(v > 0 for v in stack[4].plan.tier_counts().values())
    report = cs.serve_phase(stack, stack[0].device_arrays(),
                            fuse_aggregate=fuse_aggregate)
    assert report["requests"] == 4 and report["shed"] == 0
    assert report["out_max_err"] < 1e-4       # CPU matmuls are full fp32
    if fuse_aggregate:
        assert report["agg_max_err"] < 1e-4
        assert report["store"]["fused_aggregates"] > 0


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path,
                                                from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; without it
    the cache goes to the fixed ``.jax_cache`` at the checkout root."""
    import jax

    from repro.launch.serve import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = use_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
