"""Pallas TPU kernel fusing tier-aware row gather with segment aggregation.

The serve path's largest tensor is the sampled-neighbor feature matrix:
``tiered_gather`` writes a dense (n_sampled, d) gather result to HBM and the
model's first aggregation layer immediately reads it back to reduce each
fan-sized segment — two full trips through memory for data that is consumed
exactly once. This kernel folds the segment reduction into the gather: per
(tier, slot)-addressed child it DMAs the row straight from whichever tier
buffer owns it (HOT replica, WARM shard, or the compact pre-resolved cold
buffer) into a VMEM slab, then sums the slab over the fan axis into the
per-seed output segment. The dense neighbor tensor never reaches HBM.

Addressing: ``tier``/``slot`` are (S, fan) int32 with one row per output
segment. Tier codes 0=hot, 1=warm, 2=cold-buffer; anything else (the
wrapper pads with 99, invalid children carry 99) contributes a zero row — a
degree-0 segment therefore yields an exact zero row, matching
``segment_spmm`` semantics. Accumulation is sequential fp32 over the fan
axis, the same order as ``tiered_gather``+``segment_spmm``, so the fused
result is bit-identical to that two-kernel composition. Each grid step
takes its ``block_rows × fan`` addresses into SMEM; the whole address
matrix never has to fit there.

Grid: (segment_blocks, dim_blocks). The second axis tiles the (lane-padded)
feature dimension in ``block_dim`` columns so the autotune harness can trade
VMEM slab footprint against grid overhead; per-column accumulation order is
unchanged, so tiling never perturbs the numerics. On TPU ``block_rows`` is
a multiple of 8 and ``block_dim`` a multiple of 128 (DMA lane tiling).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiered_gather.kernel import lane_pad


def _gather_agg_kernel(tier_ref, slot_ref, hot_ref, warm_ref, cold_ref,
                       o_ref, buf, sem, *, fan: int, block_dim: int):
    r = o_ref.shape[0]
    jd = pl.program_id(1) * block_dim
    tables = (hot_ref, warm_ref, cold_ref)
    zero = jnp.zeros((1, block_dim), buf.dtype)

    def row_copy(src_ref, s, n, i):
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(s, 1), pl.ds(jd, block_dim)],
            buf.at[n, pl.ds(i, 1), :], sem)

    def start(k, _):
        t = tier_ref[0, k]
        s = slot_ref[0, k]
        i, n = k // fan, k % fan
        for code, src in enumerate(tables):
            @pl.when(t == code)
            def _():
                row_copy(src, s, n, i).start()

        @pl.when((t < 0) | (t > 2))
        def _():
            buf[n, pl.ds(i, 1), :] = zero

        return 0

    def wait(k, _):
        t = tier_ref[0, k]

        @pl.when((t >= 0) & (t <= 2))
        def _():
            row_copy(hot_ref, 0, k % fan, k // fan).wait()

        return 0

    jax.lax.fori_loop(0, r * fan, start, 0)
    jax.lax.fori_loop(0, r * fan, wait, 0)
    acc = jnp.zeros((r, block_dim), jnp.float32)
    for n in range(fan):
        acc = acc + buf[n].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def gather_aggregate_pallas(tier: jnp.ndarray, slot: jnp.ndarray,
                            hot: jnp.ndarray, warm: jnp.ndarray,
                            cold: jnp.ndarray, *,
                            block_rows: int = 8,
                            block_dim: int = 0,
                            interpret: bool = True) -> jnp.ndarray:
    """tier/slot: (S, fan) int32 (tier 0=hot, 1=warm, 2=cold, else → zero
    contribution); hot: (H, d); warm: (W, d); cold: (K, d). Returns (S, d):
    per-segment sums of the addressed rows. Slots are clamped into their
    table, as in the oracle. ``block_dim`` ≤ 0 or a non-divisor of the
    lane-padded width disables feature-dim tiling (single dim block)."""
    s, fan = tier.shape
    d = hot.shape[1]
    if s == 0 or d == 0:
        return jnp.zeros((s, d), hot.dtype)
    if fan == 0:
        return jnp.zeros((s, d), hot.dtype)
    hot_p, warm_p, cold_p = lane_pad(hot), lane_pad(warm), lane_pad(cold)
    dp = hot_p.shape[1]
    if block_dim <= 0 or dp % block_dim:
        block_dim = dp
    nb = -(-s // block_rows)
    ndb = dp // block_dim
    pad = nb * block_rows - s
    limit = jnp.where(tier == 0, hot.shape[0],
                      jnp.where(tier == 1, warm.shape[0], cold.shape[0]))
    slot = jnp.clip(slot, 0, limit - 1)
    # (nb, 1, block_rows * fan): one SMEM address block per segment block
    tier_p = jnp.pad(tier, ((0, pad), (0, 0)), constant_values=99).reshape(
        nb, 1, block_rows * fan)
    slot_p = jnp.pad(slot, ((0, pad), (0, 0))).reshape(
        nb, 1, block_rows * fan)
    addr = pl.BlockSpec((None, 1, block_rows * fan), lambda i, j: (i, 0, 0),
                        memory_space=pltpu.SMEM)

    kernel = functools.partial(_gather_agg_kernel, fan=fan,
                               block_dim=block_dim)
    out = pl.pallas_call(
        kernel,
        grid=(nb, ndb),
        in_specs=[
            addr,
            addr,
            pl.BlockSpec(memory_space=pl.ANY),     # hot replica in HBM
            pl.BlockSpec(memory_space=pl.ANY),     # warm shard in HBM
            pl.BlockSpec(memory_space=pl.ANY),     # resolved cold rows
        ],
        out_specs=pl.BlockSpec((block_rows, block_dim), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, dp), hot.dtype),
        scratch_shapes=[pltpu.VMEM((fan, block_rows, block_dim), hot.dtype),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tier_p, slot_p, hot_p, warm_p, cold_p)
    return out[:s, :d]
