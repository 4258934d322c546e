#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/control.py --workload products.mixed --seconds 6 \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22

Builds the cell once, then for each seed serves a short window at the
cell's own load with that seed's weights and traffic, and reads on the
captured requests:

* the program's numbers: bad sample slots, row mismatches and the output
  gap against the float32 reference at ``highest`` and at ``default``
  matmul precision;
* the control's: the reference put in the program's place one precision
  down (bfloat16 rows, weights and arithmetic), read the same way.

One JSON line per seed, then the largest program reading and the
smallest control reading of each number. The benchmark's runs never run
this; it is how the limits in ``bench/configs`` were set.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as bench_run  # noqa: E402


def readings(caps, model, params, data, fanouts) -> dict:
    from bench.lib import harness, reference

    out = {}
    for prec in ("highest", "default"):
        nums, per_exec = harness.check(caps, model, params, data, fanouts,
                                       {}, prec)
        out[f"program_gap_{prec}"] = nums["output_gap"]
    out.update(sample_bad_slots=nums["sample_bad_slots"],
               row_mismatches=nums["row_mismatches"], checked=per_exec)
    ctrl = []
    for c in caps:
        rows, o = reference.control_output(model, params, c["hops"],
                                           data[2], fanouts,
                                           c["out"].shape[0])
        ctrl.append({**c, "rows": rows, "out": o})
    for prec in ("highest", "default"):
        nums, _ = harness.check(ctrl, model, params, data, fanouts, {},
                                prec)
        out[f"control_gap_{prec}"] = nums["output_gap"]
    out["control_row_mismatches"] = nums["row_mismatches"]
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=6.0)
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    from bench.lib import cells
    cell = cells.workload(args.workload)
    cfg = cells.config(cell["config"])
    mix = cells.mix(cell)
    jax = bench_run.setup_jax(int(cell["chips"]), os.path.join(
        cells.cache_dir(), "jax"))
    if bench_run.find_chips(jax, int(cell["chips"])) is None:
        return 3
    from bench.lib import harness
    from bench.lib import traffic as tr

    rec = harness.Recorder(trace=False)
    system = harness.System(cfg, seeds[0], rec, log=bench_run.log)
    draw = tr.SeedDraw(system.out_degree, mix["popularity"])
    harness.warm_up(system, mix, args.seconds, draw, rec,
                    log=bench_run.log)
    data = (system.indptr, system.indices, system.feats)
    rows = []
    for seed in seeds:
        system.use_params(system.model_mod.make_params(seed, cfg))
        reqs, _ = harness.drive(system, mix, args.seconds, seed, draw, rec)
        caps = harness.host_captures(reqs)
        r = {"seed": seed, "requests": len(reqs),
             "failed": harness.failed(reqs),
             **readings(caps, system.model_mod, system.params, data,
                        system.fanouts)}
        rows.append(r)
        print(json.dumps(r), flush=True)
    keys = ("program_gap_highest", "program_gap_default")
    summary = {f"max_{k}": max(r[k] for r in rows) for k in keys}
    for k in ("control_gap_highest", "control_gap_default",
              "control_row_mismatches"):
        summary[f"min_{k}"] = min(r[k] for r in rows)
    summary["max_sample_bad_slots"] = max(r["sample_bad_slots"] for r in rows)
    summary["max_row_mismatches"] = max(r["row_mismatches"] for r in rows)
    print(json.dumps({"summary": summary}), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
