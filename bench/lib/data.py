"""The cell's dataset: a seeded power-law graph and its feature table.

Both are fixed by the configuration's ``data_seed`` (never by ``--seed``)
and are generated once per checkout into ``bench/.cache/data/``; later
runs of the cell load them. The generator is the benchmark's own copy of
the program's ``power_law_graph`` (same draws, same shape statistics), so
that a change to the program cannot change the data it is measured on.
Neighbour lists are sorted by destination, which the sampling check uses
for its binary search; the samplers accept any order.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from bench.lib import cells


def power_law_edges(num_nodes: int, avg_degree: float, *, exponent: float,
                    seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Directed edges with zipf out-degrees and zipf-ranked in-popularity
    over a random node permutation, self loops dropped."""
    rng = np.random.default_rng(seed)
    num_edges = int(num_nodes * avg_degree)
    base = rng.zipf(2.0, size=num_nodes).astype(np.float64)
    cap = max(num_nodes // 4, 8)
    base = np.minimum(base, cap)
    out_deg = np.maximum(
        np.round(base * (avg_degree / max(base.mean(), 1e-9))), 1
    ).astype(np.int64)
    out_deg = np.minimum(out_deg, cap)
    deficit = num_edges - int(out_deg.sum())
    if deficit > 0:
        np.add.at(out_deg, rng.integers(0, num_nodes, size=deficit), 1)
    src = np.repeat(np.arange(num_nodes), out_deg)
    ranks = rng.permutation(num_nodes)
    weights = 1.0 / np.power(np.arange(1, num_nodes + 1, dtype=np.float64),
                             exponent)
    weights /= weights.sum()
    dst = ranks[rng.choice(num_nodes, size=src.shape[0], p=weights)]
    keep = src != dst
    return src[keep], dst[keep]


def sorted_csr(src: np.ndarray, dst: np.ndarray, num_nodes: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """(indptr int64, indices int32) with each row's neighbours ascending."""
    key = src.astype(np.int64) * num_nodes + dst.astype(np.int64)
    key.sort()
    indptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(np.bincount(key // num_nodes, minlength=num_nodes),
              out=indptr[1:])
    return indptr, (key % num_nodes).astype(np.int32)


def features(num_nodes: int, feat_dim: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    return rng.standard_normal((num_nodes, feat_dim), dtype=np.float32)


def _key(cfg: dict) -> str:
    ds = {k: cfg[k] for k in ("num_nodes", "avg_degree", "exponent",
                               "feat_dim", "data_seed")}
    blob = json.dumps(ds, sort_keys=True).encode()
    return f"{cfg['name']}-{hashlib.sha256(blob).hexdigest()[:12]}"


def _save(path: str, arr: np.ndarray) -> None:
    tmp = path + ".part.npy"
    np.save(tmp, arr)
    os.replace(tmp, path)


def load(cfg: dict, root: str | None = None
         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(indptr, indices, feats) of the configuration, from the checkout's
    cache when present, else generated and cached."""
    d = os.path.join(cells.cache_dir(root), "data", _key(cfg))
    names = ("indptr", "indices", "feats")
    paths = [os.path.join(d, f"{n}.npy") for n in names]
    if all(os.path.exists(p) for p in paths):
        return tuple(np.load(p) for p in paths)
    os.makedirs(d, exist_ok=True)
    src, dst = power_law_edges(cfg["num_nodes"], cfg["avg_degree"],
                               exponent=cfg["exponent"],
                               seed=cfg["data_seed"])
    indptr, indices = sorted_csr(src, dst, cfg["num_nodes"])
    del src, dst
    feats = features(cfg["num_nodes"], cfg["feat_dim"], cfg["data_seed"])
    for p, arr in zip(paths, (indptr, indices, feats)):
        _save(p, arr)
    return indptr, indices, feats
