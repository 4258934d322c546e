"""Plain reference for what the timed path produced, request by request.

Three layers are checked on requests captured inside the window:

* sampling: every sampled slot is what the CSR allows (a node of degree
  at most the fanout yields its whole neighbour list in order and then
  padding; a larger one yields neighbours only; padding yields padding);
* feature collection: every collected row is bit-identical to the
  feature table's row, from whichever tier served it;
* the model: the served output against the served model's own plain
  ``jax.numpy`` ``reference`` (``bench/models/<model>.py``) on the same
  sampled subgraph and the table's rows.

Nothing here imports the program. ``sage_reference``, the GraphSAGE
reference that lived here, is ``bench/models/graphsage-mean.py``'s and
stays importable from this module under its old name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _jitted(reference):
    return jax.jit(reference,
                   static_argnames=("fanouts", "dtype", "precision"))


def __getattr__(name: str):
    if name == "sage_reference":
        from bench.lib import cells
        return cells.model("graphsage-mean").sage_reference
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


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


def output_gap(model, params, out: np.ndarray, hops, feats: np.ndarray,
               fanouts, *, precision: str = "highest") -> float:
    """Largest |served − reference| over the request's output rows;
    ``model`` is the served model's module (``cells.model``)."""
    ref = _jitted(model.reference)(
        params, [rows_of(feats, h) for h in hops],
        [np.asarray(h) for h in hops], tuple(fanouts), dtype=jnp.float32,
        precision=precision)
    n = out.shape[0]
    return float(np.abs(np.asarray(out, np.float64)
                        - np.asarray(ref[:n], np.float64)).max(initial=0.0))


def control_output(model, params, hops, feats: np.ndarray, fanouts, n: int,
                   dtype=jnp.bfloat16) -> tuple[list, np.ndarray]:
    """The model's reference put in the program's place one precision
    down: the rows it would collect and the output it would serve."""
    rows = [jnp.asarray(rows_of(feats, h), dtype) for h in hops]
    out = _jitted(model.reference)(
        params, rows, [np.asarray(h) for h in hops], tuple(fanouts),
        dtype=dtype, precision="default")
    return ([np.asarray(r.astype(jnp.float32)) for r in rows],
            np.asarray(out[:n].astype(jnp.float32)))
