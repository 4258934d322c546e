#!/usr/bin/env python3
"""The knee of an open-loop cell, found once on the chip.

    python bench/sweep.py --workload products.mixed --seconds 10 \
        --rates 20,40,60,80,100 --seed 7

Builds the cell once and serves its mix at each offered rate in turn.
Per rate it prints the offered and completed rates, the latency median
and 95th percentile, and whether a backlog grew: the median latency of
the window's last quarter of requests against its first quarter. The
knee is the highest rate whose completed rate matches the offered one
with no growing backlog; the cell's ``rate_rps`` in ``bench/cells`` is set
to 4/5 of it. The
benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as bench_run  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=7)
    args = p.parse_args()
    rates = [float(r) for r in args.rates.split(",")]

    from bench.lib import cells
    cell = cells.workload(args.workload)
    cfg = cells.config(cell["config"])
    mix = cells.mix(cell)
    jax = bench_run.setup_jax(int(cell["chips"]), os.path.join(
        cells.cache_dir(), "jax"))
    if bench_run.find_chips(jax, int(cell["chips"])) is None:
        return 3
    import numpy as np

    from bench.lib import harness
    from bench.lib import traffic as tr
    from bench.lib.stats import quantile

    rec = harness.Recorder(trace=False)
    system = harness.System(cfg, args.seed, rec, log=bench_run.log)
    draw = tr.SeedDraw(system.out_degree, mix["popularity"])
    top = copy.deepcopy(mix)           # a window long enough to send every
    top["rate_rps"] = max(rates + [1000 / args.seconds])   # size of any rate
    harness.warm_up(system, top, args.seconds, draw, rec,
                    log=bench_run.log)
    for rate in rates:
        m = copy.deepcopy(mix)
        m["rate_rps"] = rate
        reqs, t0 = harness.drive(system, m, args.seconds,
                                 args.seed, draw, rec)
        t_end = t0 + args.seconds
        done = [r for r in reqs if r.error is None and not np.isnan(r.done)]
        lat = [r.done - r.due for r in done]
        q = max(len(reqs) // 4, 1)
        first = [r.done - r.due for r in reqs[:q] if r in done]
        last = [r.done - r.due for r in reqs[-q:] if r in done]
        row = {"rate": rate, "offered": len(reqs) / args.seconds,
               "completed_in_window": sum(r.done <= t_end for r in done)
               / args.seconds,
               "failed": harness.failed(reqs),
               "p50_ms": quantile(lat, 0.5) * 1e3,
               "p95_ms": quantile(lat, 0.95) * 1e3,
               "first_quarter_p50_ms": quantile(first, 0.5) * 1e3,
               "last_quarter_p50_ms": quantile(last, 0.5) * 1e3,
               "gen_lag_p95_ms": quantile([r.sent - r.due for r in reqs],
                                          0.95) * 1e3,
               "device_share": sum(r.executor == "device" for r in done)
               / max(len(done), 1)}
        print(json.dumps(row), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
