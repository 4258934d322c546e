"""The one general load generator: turns a traffic file into requests.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

    loop            "open" (a schedule of due times) or "closed" (clients
                    that each wait for their reply before sending again)
    rate_rps        open loop: offered requests per second; the knee of
                    one cell, so each cell sets it in ``bench/cells``
    clients         closed loop: number of clients
    seeds           {"dist": "log_uniform", "min": a, "max": b} or
                    {"dist": "fixed", "value": n}
    popularity      "out_degree": seeds drawn out-degree-weighted (the
                    program's ``WorkloadGenerator`` draw, paper §6.1)
    capture         requests kept for the correctness check
    capture_span    closed loop: the first sends the capture is drawn from

An open-loop run of ``seconds`` gets the same multiset of request sizes
and of inter-arrival gaps for every seed (evenly spaced quantiles of the
size distribution and of the exponential), in an order, and with seed
nodes, drawn from the seed: the offered work is the same, its order is
not.
"""
from __future__ import annotations

import math

import numpy as np


class SeedDraw:
    """Seed nodes drawn by popularity from a fixed CDF."""

    def __init__(self, out_degree: np.ndarray, popularity: str):
        if popularity != "out_degree":
            raise ValueError(f"unknown popularity {popularity!r}")
        w = out_degree.astype(np.float64) + 1e-6
        self.cdf = np.cumsum(w / w.sum())

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        u = rng.random(n) * self.cdf[-1]
        return np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          self.cdf.shape[0] - 1).astype(np.int64)


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def size_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` request sizes at evenly spaced quantiles of the distribution."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "log_uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        u = _midpoints(n)
        sizes = np.floor(np.exp(math.log(lo) + u * (math.log(hi + 1)
                                                     - math.log(lo))))
        return np.clip(sizes, lo, hi).astype(np.int64)
    raise ValueError(f"unknown size distribution {spec['dist']!r}")


def open_count(traffic: dict, seconds: float) -> int:
    """Requests an open-loop window of ``seconds`` sends."""
    return max(int(round(float(traffic["rate_rps"]) * seconds)), 1)


def open_schedule(traffic: dict, seconds: float, seed: int,
                  draw: SeedDraw) -> tuple[np.ndarray, list[np.ndarray]]:
    """(due times in seconds from the window start, seed arrays)."""
    n = open_count(traffic, seconds)
    rng = np.random.default_rng([seed, 0])
    gaps = -np.log1p(-_midpoints(n))
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    sizes = size_quantiles(traffic["seeds"], n)[rng.permutation(n)]
    seeds = draw.draw(rng, int(sizes.sum()))
    return due, np.split(seeds, np.cumsum(sizes)[:-1])


def client_stream(traffic: dict, seed: int, client: int, draw: SeedDraw):
    """Endless seed arrays of one closed-loop client."""
    rng = np.random.default_rng([seed, 1, client])
    size = size_quantiles(traffic["seeds"], 1)[0]
    while True:
        yield draw.draw(rng, int(size))


def capture_set(traffic: dict, seed: int, sizes: np.ndarray | None
                ) -> set[int]:
    """Request indices kept for the check: ``capture`` drawn from the
    seed, with the first of the largest requests always among them."""
    rng = np.random.default_rng([seed, 2])
    k = int(traffic["capture"])
    if sizes is None:                    # closed loop: first sends
        span = int(traffic["capture_span"])
        return set(rng.choice(span, size=min(k, span), replace=False)
                   .tolist())
    n = sizes.shape[0]
    keep = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    keep.add(int(np.argmax(sizes)))
    return keep
