"""Model step: the model FLOPs the window's served requests require (from
the shapes, useful seeds only) over the sum of their executor ``run``
spans, as a share of the chip's peak bf16 FLOP/s."""
import math


def read(run):
    done = [r for r in run.completed if not math.isnan(r.start)]
    busy = sum(r.end - r.start for r in done)
    if not done or busy <= 0 or not run.peaks:
        return None
    flops = run.flops_per_seed * sum(r.seeds.shape[0] for r in done)
    return 100.0 * flops / busy / run.peaks["bf16_flops_per_s"]
