"""The served GraphSAGE's weights and the ``infer_fn`` handed to the
executors.

The benchmark makes the weights itself, from ``--seed``, on the device in
one jitted call and in the type they are served in (float32), so that the
plain reference never takes weights the program made. The model code is
the program's own ``sage_layered``; weights are an argument of the
compiled program, so every seed reuses one compilation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn_basic import sage_layered


def key_of(seed: int, salt: int = 0) -> jax.Array:
    """A JAX key from any non-negative integer seed (64 bits and beyond:
    ``jax.random.key`` alone would drop the high bits)."""
    words = np.random.SeedSequence([seed, salt]).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


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


def make_params(seed: int, feat_dim: int, hidden) -> dict:
    """float32 GraphSAGE weights from ``seed``, made on the device."""
    return _init(key_of(seed, 1), (int(feat_dim), *map(int, hidden)))


@partial(jax.jit, static_argnames=("fanouts",))
def _infer(params, hop_feats, hop_ids, fanouts):
    masks = [(h >= 0).astype(jnp.float32)[:, None] for h in hop_ids]
    return sage_layered(params, hop_feats, fanouts, hop_masks=masks)


class Served:
    """The ``infer_fn(hop_feats, hop_ids)`` the executors call; its
    weights can be swapped between windows without a new compilation."""

    def __init__(self, params: dict, fanouts):
        self.params = params
        self.fanouts = tuple(int(f) for f in fanouts)

    def __call__(self, hop_feats, hop_ids, deep_agg=None):
        if deep_agg is not None:
            raise ValueError("the benchmark serves fuse_aggregate=False")
        return _infer(self.params, list(hop_feats), list(hop_ids),
                      self.fanouts)
