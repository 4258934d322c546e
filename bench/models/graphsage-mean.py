"""GraphSAGE with the mean aggregator, as the benchmark serves and checks it.

The four functions every file of ``bench/models`` gives (see
``bench/lib/cells.py``):

* ``make_params(seed, cfg)``: the weights, made by the benchmark itself
  from ``--seed``, on the device in one jitted call and in the type they
  are served in (float32), so that the plain reference never takes
  weights the program made;
* ``served(params, cfg)``: the ``infer_fn(hop_feats, hop_ids,
  deep_agg=None)`` handed to the executors. The model code is the
  program's own ``sage_layered``; weights are an argument of the compiled
  program, so every seed reuses one compilation;
* ``reference(params, hop_rows, hop_ids, fanouts, *, dtype, precision)``:
  a plain ``jax.numpy`` GraphSAGE (mean aggregator, layer norm, ReLU
  between layers) that imports nothing of the program; it follows the
  program's ``chip_smoke.sage_reference``;
* ``flops_per_seed(cfg)``: the model FLOPs one seed requires.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.model import PRECISIONS, key_of
from repro.models.gnn_basic import sage_layered


@partial(jax.jit, static_argnames=("dims",))
def _init(key, dims):
    layers = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, *ks = jax.random.split(key, 7)
        scale = 1.0 / np.sqrt(d_in)
        layers.append({
            "self": {"w": jax.random.normal(ks[0], (d_in, d_out)) * scale,
                     "b": jax.random.normal(ks[1], (d_out,)) * 0.1},
            "neigh": {"w": jax.random.normal(ks[2], (d_in, d_out)) * scale,
                      "b": jax.random.normal(ks[3], (d_out,)) * 0.1},
            "ln": {"g": 1.0 + jax.random.normal(ks[4], (d_out,)) * 0.1,
                   "b": jax.random.normal(ks[5], (d_out,)) * 0.1}})
    return {"layers": layers}


def make_params(seed: int, cfg: dict) -> dict:
    """float32 GraphSAGE weights from ``seed``, made on the device."""
    return _init(key_of(seed, 1),
                 (int(cfg["feat_dim"]), *map(int, cfg["hidden"])))


@partial(jax.jit, static_argnames=("fanouts",))
def _infer(params, hop_feats, hop_ids, fanouts):
    masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
    return sage_layered(params, hop_feats, fanouts, hop_masks=masks)


class Served:
    """The ``infer_fn(hop_feats, hop_ids)`` the executors call."""

    def __init__(self, params: dict, fanouts):
        self.params = params
        self.fanouts = tuple(int(f) for f in fanouts)

    def __call__(self, hop_feats, hop_ids, deep_agg=None):
        if deep_agg is not None:
            raise ValueError("the benchmark serves fuse_aggregate=False")
        return _infer(self.params, list(hop_feats), list(hop_ids),
                      self.fanouts)


def served(params: dict, cfg: dict) -> Served:
    return Served(params, cfg["fanouts"])


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


reference = sage_reference


def sage_flops_per_seed(feat_dim: int, hidden, fanouts) -> int:
    """Model FLOPs one seed requires in the layered GraphSAGE: layer l runs
    its two matmuls (self and neighbour, 2 FLOPs a multiply-add) at every
    node of levels 0 .. L-1-l, where level k holds prod(fanouts[:k])
    nodes per seed."""
    dims = [int(feat_dim), *map(int, hidden)]
    n_layers = len(dims) - 1
    nodes = np.cumprod([1, *map(int, fanouts)])
    return int(sum(int(nodes[:n_layers - layer].sum()) * 2 * 2
                   * dims[layer] * dims[layer + 1]
                   for layer in range(n_layers)))


def flops_per_seed(cfg: dict) -> int:
    return sage_flops_per_seed(cfg["feat_dim"], cfg["hidden"],
                               cfg["fanouts"])
