"""Pallas TPU EmbeddingBag — the recsys/feature-aggregation hot path.

JAX has no native EmbeddingBag; this is the TPU kernel for
``out[b] = reduce_{j∈bag_b} w_bj · table[ids[b, j]]`` with sum/mean modes.
Same ELL-style dataflow as segment_spmm: the id/weight tile lives in VMEM,
the (possibly huge) table stays in HBM and rows stream in via dynamic-slice
DMAs; one destination row per kernel row, fp32 accumulation in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _bag_kernel(ids_ref, w_ref, table_ref, o_ref, acc_ref, cnt_ref, *,
                bag: int, weighted: bool, mean: bool):
    r = o_ref.shape[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    cnt_ref[...] = jnp.zeros_like(cnt_ref)

    def row_body(i, _):
        def bag_body(n, _):
            idx = ids_ref[i, n]
            valid = idx >= 0
            row = table_ref[pl.ds(jnp.maximum(idx, 0), 1), :].astype(
                jnp.float32)
            w = jnp.where(valid, 1.0, 0.0)
            if weighted:
                w = w * w_ref[i, n].astype(jnp.float32)
            acc_ref[pl.ds(i, 1), :] += row * w
            cnt_ref[pl.ds(i, 1), :] += jnp.where(valid, 1.0, 0.0)
            return 0

        jax.lax.fori_loop(0, bag, bag_body, 0)
        return 0

    jax.lax.fori_loop(0, r, row_body, 0)
    out = acc_ref[...]
    if mean:
        out = out / jnp.maximum(cnt_ref[...][:, :1], 1.0)
    o_ref[...] = out.astype(o_ref.dtype)


def embedding_bag_pallas(table: jnp.ndarray, ids: jnp.ndarray,
                         weights: jnp.ndarray | None = None, *,
                         mode: str = "sum", block_rows: int = 8,
                         interpret: bool = True) -> jnp.ndarray:
    """table: (V, d); ids: (B, bag) int32 (-1 pad); weights: (B, bag)|None."""
    bsz, bag = ids.shape
    d = table.shape[1]
    if bsz == 0 or bag == 0 or d == 0:
        # empty grid / zero-length dynamic slices are rejected by
        # pallas_call; an empty bag reduces to zeros (mean guard included),
        # like the oracle
        return jnp.zeros((bsz, d), table.dtype)
    nb = -(-bsz // block_rows)
    pad = nb * block_rows - bsz
    ids_p = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    w_p = (jnp.pad(weights, ((0, pad), (0, 0))) if weights is not None
           else jnp.zeros((nb * block_rows, bag), table.dtype))

    kernel = functools.partial(_bag_kernel, bag=bag,
                               weighted=weights is not None,
                               mean=mode == "mean")
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, bag), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, bag), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d), table.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, d), jnp.float32),
                        pltpu.VMEM((block_rows, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids_p, w_p, table)
    return out[:bsz]
