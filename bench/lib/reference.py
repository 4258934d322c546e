"""Plain reference for what the timed path produced, request by request.

Three layers are checked on requests captured inside the window:

* sampling: every sampled slot is what the CSR allows (a node of degree
  at most the fanout yields its whole neighbour list in order and then
  padding; a larger one yields neighbours only; padding yields padding);
* feature collection: every collected row is bit-identical to the
  feature table's row, from whichever tier served it;
* the model: the served output against a plain ``jax.numpy`` float32
  GraphSAGE (mean aggregator, layer norm, ReLU between layers) on the
  same sampled subgraph and the table's rows.

Nothing here imports the program. ``sage_reference`` follows the
program's ``chip_smoke.sage_reference``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}


def sage_reference(params, hop_rows, hop_ids, fanouts, *,
                   dtype=jnp.float32, precision: str = "highest"):
    """Layered GraphSAGE computed in ``dtype`` at matmul ``precision``."""
    hp = PRECISIONS[precision]
    cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    h = [cast(r) for r in hop_rows]
    masks = [cast(jnp.asarray(i) >= 0) for i in hop_ids]
    n_layers = len(params["layers"])
    for layer, p in enumerate(params["layers"]):
        p = jax.tree.map(cast, p)
        nxt = []
        for lvl in range(n_layers - layer):
            fan = fanouts[lvl]
            child = h[lvl + 1].reshape(h[lvl].shape[0], fan, -1)
            m = masks[lvl + 1].reshape(h[lvl].shape[0], fan, 1)
            agg = (child * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
            z = (jnp.dot(h[lvl], p["self"]["w"], precision=hp)
                 + p["self"]["b"]
                 + jnp.dot(agg, p["neigh"]["w"], precision=hp)
                 + p["neigh"]["b"])
            mu = z.mean(-1, keepdims=True)
            var = ((z - mu) ** 2).mean(-1, keepdims=True)
            z = (z - mu) / jnp.sqrt(var + 1e-5) * p["ln"]["g"] + p["ln"]["b"]
            nxt.append(z if layer == n_layers - 1 else jnp.maximum(z, 0.0))
        h = nxt
    return h[0]


_reference_jit = jax.jit(sage_reference,
                         static_argnames=("fanouts", "dtype", "precision"))


def rows_of(feats: np.ndarray, ids: np.ndarray) -> np.ndarray:
    ids = np.asarray(ids)
    return np.where((ids >= 0)[:, None], feats[np.maximum(ids, 0)],
                    0).astype(feats.dtype)


def bad_sample_slots(seeds: np.ndarray, hops, fanouts, indptr: np.ndarray,
                     indices: np.ndarray) -> int:
    """Slots of a layered sample the CSR does not allow (0 when sound).
    ``indices`` must hold each row's neighbours in ascending order."""
    seeds = np.asarray(seeds)
    h0 = np.asarray(hops[0])
    n = seeds.shape[0]
    bad = int((h0[:n] != seeds).sum() + (h0[n:] != -1).sum())
    e_max = max(indices.shape[0] - 1, 0)
    for k, fan in enumerate(fanouts):
        parent = np.asarray(hops[k]).astype(np.int64)
        child = np.asarray(hops[k + 1]).astype(np.int64).reshape(-1, fan)
        valid = parent >= 0
        v = np.maximum(parent, 0)
        start, end = indptr[v], indptr[v + 1]
        deg = np.where(valid, end - start, 0)
        j = np.arange(fan)[None, :]
        whole = np.where(j < deg[:, None],
                         indices[np.minimum(start[:, None] + j, e_max)], -1)
        few = deg <= fan
        bad += int((child[few] != whole[few]).sum())
        many = ~few
        if many.any():
            c = child[many]
            lo = np.broadcast_to(start[many][:, None], c.shape).copy()
            hi = np.broadcast_to(end[many][:, None], c.shape).copy()
            stop = hi.copy()
            while (lo < hi).any():           # lower bound of c in its row
                active = lo < hi
                mid = (lo + hi) // 2
                less = indices[np.minimum(mid, e_max)] < c
                lo = np.where(active & less, mid + 1, lo)
                hi = np.where(active & ~less, mid, hi)
            found = (lo < stop) & (indices[np.minimum(lo, e_max)] == c)
            bad += int((~found).sum())
    return bad


def row_mismatches(hop_rows, hops, feats: np.ndarray) -> int:
    """Collected rows that differ from the table's row (padding must be
    zeros)."""
    return int(sum((np.asarray(r) != rows_of(feats, h)).any(axis=1).sum()
                   for r, h in zip(hop_rows, hops)))


def output_gap(params, out: np.ndarray, hops, feats: np.ndarray, fanouts, *,
               precision: str = "highest") -> float:
    """Largest |served − reference| over the request's output rows."""
    ref = _reference_jit(params, [rows_of(feats, h) for h in hops],
                         [np.asarray(h) for h in hops], tuple(fanouts),
                         precision=precision)
    n = out.shape[0]
    return float(np.abs(np.asarray(out, np.float64)
                        - np.asarray(ref[:n], np.float64)).max(initial=0.0))


def control_output(params, hops, feats: np.ndarray, fanouts, n: int,
                   dtype=jnp.bfloat16) -> tuple[list, np.ndarray]:
    """The reference put in the program's place one precision down:
    the rows it would collect and the output it would serve."""
    rows = [jnp.asarray(rows_of(feats, h), dtype) for h in hops]
    out = _reference_jit(params, rows, [np.asarray(h) for h in hops],
                         tuple(fanouts), dtype=dtype, precision="default")
    return ([np.asarray(r.astype(jnp.float32)) for r in rows],
            np.asarray(out[:n].astype(jnp.float32)))
