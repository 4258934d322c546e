#!/usr/bin/env python3
"""How far the program's calibration moves its routing, on the chip.

    python bench/calibration.py --config products-sage2 --times 4 \
        --settings '{}' '{"repeats": 8, "tail": 0.75}'

Builds the configuration's serving path once and warms it up, then runs
the program's ``calibrate_executors`` ``--times`` times under each
setting (keys laid over the configuration's ``calibration``). After each
calibration it prints the host/device PSGS cut-point and the share of
each cell's window that the router built from it would send to the host.
A setting whose shares stay put from one calibration to the next gives
cells that measure one routing, not a coin toss. The benchmark's runs
never run this; it is how the ``calibration`` of ``bench/configs`` was
chosen.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as bench_run  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--settings", nargs="+", default=["{}"])
    p.add_argument("--times", type=int, default=4)
    p.add_argument("--seconds", type=float, default=32.0)
    p.add_argument("--seed", type=int, default=7)
    args = p.parse_args()

    from bench.lib import cells
    cfg = cells.config(args.config)
    mine = [w for w in cells.benchmark()["workloads"]
            if w["config"] == args.config]
    mixes = {w["name"]: cells.mix(w) for w in mine}
    jax = bench_run.setup_jax(1, os.path.join(cells.cache_dir(), "jax"))
    if bench_run.find_chips(jax, 1) is None:
        return 3
    import numpy as np

    from bench.lib import harness
    from bench.lib import traffic as tr
    from repro.serving import CostModelRouter, calibrate_executors

    rec = harness.Recorder(trace=False)
    system = harness.System(cfg, args.seed, rec, log=bench_run.log)
    draw = tr.SeedDraw(system.out_degree, "out_degree")
    windows = {}
    for name, mix in mixes.items():
        if mix["loop"] == "open":
            windows[name] = tr.open_schedule(mix, args.seconds, args.seed,
                                             draw)[1]
        else:
            rng = np.random.default_rng(args.seed)
            size = int(tr.size_quantiles(mix["seeds"], 1)[0])
            windows[name] = [draw.draw(rng, size) for _ in range(512)]
    biggest = max(mixes.values(), key=lambda m: m.get("rate_rps", 0))
    harness.warm_up(system, biggest, args.seconds, draw, rec,
                    log=bench_run.log)
    for setting in args.settings:
        over = json.loads(setting)
        cal = {**cfg["calibration"], **over}
        batches = harness.calibration_batches({"calibration": cal},
                                              system.psgs)
        for i in range(args.times):
            t = time.monotonic()
            curves = calibrate_executors(
                system.executors, batches, system.psgs,
                repeats=int(cal["repeats"]),
                tail=float(cal.get("tail", 1.0)))
            took = time.monotonic() - t
            router = CostModelRouter.from_curves(
                system.psgs, curves, cfg["serving"]["policy"],
                executors=system.executors)
            share = {}
            for name, seeds in windows.items():
                share[name] = sum(router.route(s) == "host"
                                  for s in seeds) / len(seeds)
            print(json.dumps({
                "setting": over, "i": i, "seconds": took,
                "cut": router.crossover("host", "device"),
                "host_share": share,
                "curves": {n: {"psgs": np.round(c.psgs, 1).tolist(),
                               "tail_ms": np.round(c.mx * 1e3, 2).tolist()}
                           for n, c in curves.items()}}), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
