"""Pallas TPU kernel for the one-sided-read engine's fused two-level gather.

The tiered feature store resolves each requested id to (tier, slot) via the
lookup tables (paper §5.3's "feature lookup table"). The device-resident part
of a lookup is then a *two-source* gather: hot rows come from the replicated
cache, warm rows from the local shard. Fusing the source select into one
kernel reads each row once, from the one table that owns it.

ids are pre-resolved to (tier, slot) by ops.py (two cheap (M,) gathers);
each grid step takes ``block_rows`` addresses into SMEM and starts one row
DMA per address straight from the owning HBM table into the output block.
Address-sorted ids (the paper's TLB optimization) make consecutive DMAs
near-sequential.

Mosaic only moves HBM data by DMA, and a DMA's lane extent must be a
multiple of 128. Tables are therefore lane-padded (``lane_pad``); the
feature store keeps its device tiers padded so that is a no-op on the
serve path, and the result is sliced back to the caller's width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def lane_pad(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad the last axis of a row table to a multiple of 128 lanes
    (identity when it already is one)."""
    pad = -x.shape[-1] % LANES
    return x if pad == 0 else jnp.pad(x, ((0, 0), (0, pad)))


def _tiered_kernel(tier_ref, slot_ref, hot_ref, warm_ref, o_ref, sem, *,
                   rows: int):
    zero = jnp.zeros((1, o_ref.shape[1]), o_ref.dtype)

    def row_copy(src_ref, s, i):
        return pltpu.make_async_copy(src_ref.at[pl.ds(s, 1), :],
                                     o_ref.at[pl.ds(i, 1), :], sem)

    def start(i, _):
        t = tier_ref[0, i]
        s = slot_ref[0, i]

        @pl.when(t == 0)
        def _():
            row_copy(hot_ref, s, i).start()

        @pl.when(t == 1)
        def _():
            row_copy(warm_ref, s, i).start()

        @pl.when((t != 0) & (t != 1))
        def _():
            o_ref[pl.ds(i, 1), :] = zero

        return 0

    def wait(i, _):
        t = tier_ref[0, i]

        @pl.when((t == 0) | (t == 1))
        def _():
            row_copy(hot_ref, 0, i).wait()

        return 0

    jax.lax.fori_loop(0, rows, start, 0)
    jax.lax.fori_loop(0, rows, wait, 0)


def tiered_gather_pallas(tier: jnp.ndarray, slot: jnp.ndarray,
                         hot: jnp.ndarray, warm: jnp.ndarray, *,
                         block_rows: int = 8,
                         interpret: bool = True) -> jnp.ndarray:
    """tier/slot: (M,) int32 (tier 0=hot, 1=warm, else → zeros);
    hot: (H, d); warm: (W, d). Slots are clamped into their table, as in
    the oracle. ``block_rows`` is a multiple of 8 on TPU. Returns (M, d)."""
    m = tier.shape[0]
    d = hot.shape[1]
    if m == 0 or d == 0:
        return jnp.zeros((m, d), hot.dtype)
    hot_p, warm_p = lane_pad(hot), lane_pad(warm)
    dp = hot_p.shape[1]
    nb = -(-m // block_rows)
    pad = nb * block_rows - m
    slot = jnp.clip(slot, 0, jnp.where(tier == 0, hot.shape[0],
                                       warm.shape[0]) - 1)
    # (nb, 1, block_rows): one SMEM address block per grid step
    tier_p = jnp.pad(tier, (0, pad), constant_values=99).reshape(
        nb, 1, block_rows)
    slot_p = jnp.pad(slot, (0, pad)).reshape(nb, 1, block_rows)
    addr = pl.BlockSpec((None, 1, block_rows), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)

    kernel = functools.partial(_tiered_kernel, rows=block_rows)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            addr,
            addr,
            pl.BlockSpec(memory_space=pl.ANY),     # hot replica in HBM
            pl.BlockSpec(memory_space=pl.ANY),     # warm shard in HBM
        ],
        out_specs=pl.BlockSpec((block_rows, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, dp), hot.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tier_p, slot_p, hot_p, warm_p)
    return out[:m, :d]
