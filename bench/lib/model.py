"""What the served models of ``bench/models`` share: the key their weights
are drawn from and the matmul precisions their references run at.

The GraphSAGE that lived here is ``bench/models/graphsage-mean.py``; its
``Served`` stays importable from this module under its old name.
"""
from __future__ import annotations

import jax
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}


def key_of(seed: int, salt: int = 0) -> jax.Array:
    """A JAX key from any non-negative integer seed (64 bits and beyond:
    ``jax.random.key`` alone would drop the high bits)."""
    words = np.random.SeedSequence([seed, salt]).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def __getattr__(name: str):
    if name == "Served":
        from bench.lib import cells
        return cells.model("graphsage-mean").Served
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
