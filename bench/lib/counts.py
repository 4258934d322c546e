"""Bytes of the served work, from shapes and ids alone. A model's FLOPs are
its own (``flops_per_seed`` of ``bench/models/<model>.py``);
``sage_flops_per_seed`` stays importable from here under its old name."""
from __future__ import annotations

import numpy as np

HBM_TIERS = (0, 1)     # HOT and WARM: the rows the gather kernel reads


def gather_bytes(ids: np.ndarray, tier: np.ndarray, feat_dim: int,
                 itemsize: int = 4) -> int:
    """Useful bytes of one fused lookup's HBM gather: each distinct valid
    id that its tier keeps in HBM is read once and written once, at the
    logical width (not the lane-padded one, not the padded slots)."""
    ids = np.unique(ids[ids >= 0])
    return int(np.isin(tier[ids], HBM_TIERS).sum()) * 2 * feat_dim * itemsize


def __getattr__(name: str):
    if name == "sage_flops_per_seed":
        from bench.lib import cells
        return cells.model("graphsage-mean").sage_flops_per_seed
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
