"""Pallas TPU flash-attention kernel (causal / full, GQA-aware).

Grid: (batch·heads, num_q_blocks, num_kv_blocks) with the kv dimension
"arbitrary" (sequential) so the online-softmax state lives in VMEM scratch
across kv steps. Block shapes are (block_q, head_dim) / (block_kv, head_dim)
— head_dim is kept whole (128 for every assigned arch, MXU-aligned), and the
running max/denominator are stored lane-replicated (block_q, 128) as usual on
TPU. Causal blocks strictly above the diagonal are skipped with ``pl.when``
(no FLOPs, no VREG traffic — the DMA is already amortized by the pipeline).

GQA is handled in the BlockSpec index maps: the kv block index maps query
head h → kv head h // (H // KV), so no materialized KV expansion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_kv: int,
                  seq_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # kv block strictly above the diagonal ⇒ fully masked ⇒ skip.
        run = (ik * block_kv) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                      # (bk, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kv_pos < seq_kv
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = mask & (kv_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                  # (bq, 1)
        m_cur = s.max(axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                         # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.broadcast_to(
            p.sum(axis=1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], 1e-20)).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, block_q: int = 128,
                           block_kv: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q: (B, Sq, H, dh); k/v: (B, Skv, KV, dh), H % KV == 0. Returns like q.

    ``interpret=True`` runs the kernel body on CPU (validation); on TPU pass
    ``interpret=False``.
    """
    b, sq, h, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    group = h // kv
    scale = 1.0 / np.sqrt(dh)

    block_q = min(block_q, max(sq, 8))
    block_kv = min(block_kv, max(skv, 8))
    nq = -(-sq // block_q)
    nk = -(-skv // block_kv)
    pad_q = nq * block_q - sq
    pad_kv = nk * block_kv - skv

    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * kv, skv, dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * kv, skv, dh)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_kv), (0, 0)))

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        return ((bh // h) * kv + (bh % h) // group, ik, 0)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_kv=block_kv,
                               seq_kv=skv)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), q_map),
            pl.BlockSpec((1, block_kv, dh), kv_map),
            pl.BlockSpec((1, block_kv, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, nq * block_q, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
            pltpu.VMEM((block_q, dh), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :sq].reshape(b, h, sq, dh)
    return jnp.moveaxis(out, 1, 2)
