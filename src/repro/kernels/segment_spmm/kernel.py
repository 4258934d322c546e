"""Pallas TPU kernel for GNN message aggregation (SpMM) in ELL layout.

The hot loop of every assigned GNN arch is ``out[i] = Σ_{j∈N(i)} w_ij·x[j]``.
On TPU we use the ELL (padded-neighbor) layout: ids (N, Dmax) int32 with -1
padding — fixed shapes, no data-dependent control flow, and each destination
row is owned by exactly one kernel instance (no atomics, which TPUs lack).

Grid: (num_node_blocks,). Per block: the (R, Dmax) id tile rides in VMEM, the
feature table stays in HBM (``pl.ANY``) and rows are pulled with dynamic
slices — on real TPU these become DMA gathers that the sequential grid
pipelines against the accumulation FLOPs; ``interpret=True`` validates the
same dataflow on CPU. Rows accumulate in a (R, d) fp32 VMEM scratch.

This layout choice (vs CSR two-phase sort-reduce) is the TPU adaptation of
the paper's CUDA sparse-matmul primitive used for PSGS/FAP (§4.1): degree
skew costs padding instead of warp divergence, and Quiver's own metrics tell
us the padding waste up front.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _spmm_kernel(ids_ref, w_ref, feat_ref, o_ref, acc_ref, *, dmax: int,
                 weighted: bool):
    r = o_ref.shape[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def row_body(i, _):
        def nbr_body(n, _):
            idx = ids_ref[i, n]
            valid = idx >= 0
            safe = jnp.maximum(idx, 0)
            row = feat_ref[pl.ds(safe, 1), :].astype(jnp.float32)
            w = jnp.where(valid, 1.0, 0.0)
            if weighted:
                w = w * w_ref[i, n].astype(jnp.float32)
            acc_ref[pl.ds(i, 1), :] += row * w
            return 0

        jax.lax.fori_loop(0, dmax, nbr_body, 0)
        return 0

    jax.lax.fori_loop(0, r, row_body, 0)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def segment_spmm_pallas(ids: jnp.ndarray, feat: jnp.ndarray,
                        weights: jnp.ndarray | None = None, *,
                        block_rows: int = 8,
                        interpret: bool = True) -> jnp.ndarray:
    """ids: (N, Dmax) int32 (-1 pad); feat: (M, d); weights: (N, Dmax) or
    None. Returns (N, d): per-row reduced neighbor features."""
    n, dmax = ids.shape
    d = feat.shape[1]
    if n == 0 or dmax == 0 or d == 0:
        # empty grid / zero-length dynamic slices are rejected by
        # pallas_call; an empty reduction is exactly zeros, like the oracle
        return jnp.zeros((n, d), feat.dtype)
    nb = -(-n // block_rows)
    pad = nb * block_rows - n
    ids_p = jnp.pad(ids, ((0, pad), (0, 0)), constant_values=-1)
    w = weights if weights is not None else jnp.ones((1, 1), feat.dtype)
    w_p = (jnp.pad(w, ((0, pad), (0, 0))) if weights is not None
           else jnp.zeros((nb * block_rows, dmax), feat.dtype))

    kernel = functools.partial(_spmm_kernel, dmax=dmax,
                               weighted=weights is not None)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, dmax), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, dmax), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),     # feature table in HBM
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d), feat.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ids_p, w_p, feat)
    return out[:n]
