"""Operations and bytes of the served work, from shapes and ids alone."""
from __future__ import annotations

import numpy as np

HBM_TIERS = (0, 1)     # HOT and WARM: the rows the gather kernel reads


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


def gather_bytes(ids: np.ndarray, tier: np.ndarray, feat_dim: int,
                 itemsize: int = 4) -> int:
    """Useful bytes of one fused lookup's HBM gather: each distinct valid
    id that its tier keeps in HBM is read once and written once, at the
    logical width (not the lane-padded one, not the padded slots)."""
    ids = np.unique(ids[ids >= 0])
    return int(np.isin(tier[ids], HBM_TIERS).sum()) * 2 * feat_dim * itemsize
