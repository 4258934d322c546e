"""Serve-path programs compiled for a described TPU v5e, at real widths.

No chip is attached: the TPU compiler builds each program for one chip of
a described ``v5e:2x2`` topology, which raises whatever Mosaic or XLA
would raise on the chip (unaligned DMA slices, SMEM or VMEM overflow).
Shapes are the ogbn-products cell the launcher serves: 2,449,029 nodes,
61,859,121 sampled-graph edges, d=100 (and 128), fanouts (15, 10), HOT and
WARM tables as the default placement sizes them, address vectors for
``max_batch`` 32 and 128. The topology is described only inside the
``topo`` fixture, so collection never loads the TPU library.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gather_aggregate.kernel import gather_aggregate_pallas
from repro.kernels.tiered_gather.kernel import tiered_gather_pallas

NODES, EDGES = 2_449_029, 61_859_121
HOT_ROWS, WARM_ROWS = 153_064, 459_193     # default placement, products
FANOUTS = (15, 10)
COLD_ROWS = 4096                           # pow2 cold side-table bucket


def _hop_sizes(max_batch: int) -> list[int]:
    sizes = [max_batch]
    for fan in FANOUTS:
        sizes.append(sizes[-1] * fan)
    return sizes


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → an abstract argument on one described
    chip. The persistent compile cache stays off meanwhile: entries
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("max_batch", [32, 128])
@pytest.mark.parametrize("d", [100, 128])
def test_tiered_gather_compiles_for_v5e(spec, d, max_batch):
    """The fused collection's device gather over every unique id of a
    sample (seeds + both frontiers)."""
    m = sum(_hop_sizes(max_batch))
    text = _compiled_text(
        partial(tiered_gather_pallas, interpret=False),
        spec((m,), jnp.int32), spec((m,), jnp.int32),
        spec((HOT_ROWS, d)), spec((WARM_ROWS, d)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("max_batch", [32, 128])
@pytest.mark.parametrize("d", [100, 128])
def test_gather_aggregate_compiles_for_v5e(spec, d, max_batch):
    """``lookup_aggregate``'s (total + P, fan) segment matrix: one
    singleton segment per unique id, one fan-wide segment per parent of
    the innermost hop — 231,680 addresses at ``max_batch`` 128."""
    sizes = _hop_sizes(max_batch)
    segs = (sum(sizes) + sizes[-2], FANOUTS[-1])
    text = _compiled_text(
        partial(gather_aggregate_pallas, interpret=False),
        spec(segs, jnp.int32), spec(segs, jnp.int32),
        spec((HOT_ROWS, d)), spec((WARM_ROWS, d)), spec((COLD_ROWS, d)))
    assert "tpu_custom_call" in text


def test_device_sample_and_infer_compile_for_v5e(spec):
    """The device executor's jitted halves at the products shape: padded
    on-device sampling over the whole CSR, then the sage-base GraphSAGE
    on the collected rows."""
    from repro.graph.sampler import device_sample
    from repro.launch.serve import make_infer_fn

    d, max_batch = 100, 128
    infer_fn = make_infer_fn(d, (128, 128), FANOUTS)

    def step(seed, indptr, indices, seeds, *hop_feats):
        hops = device_sample(jax.random.key(seed), indptr, indices, seeds,
                             FANOUTS)
        return infer_fn(list(hop_feats), hops)

    sizes = _hop_sizes(max_batch)
    compiled = jax.jit(step).lower(
        spec((), jnp.uint32), spec((NODES + 1,), jnp.int32),
        spec((EDGES,), jnp.int32), spec((max_batch,), jnp.int32),
        *[spec((s, d)) for s in sizes]).compile()
    out = compiled.out_info
    assert out.shape == (max_batch, 128) and out.dtype == jnp.float32
