"""Kernel: share of the roofline the ``tiered_gather`` program reached.
The least time is the useful bytes (each distinct HBM-resident row of a
lookup read and written once, at the logical width) over the chip's HBM
bandwidth; it is divided by the program's device time in the trace."""
from bench.lib.trace import module_times


def read(run):
    if run.trace is None or not run.collect_bytes or not run.peaks:
        return None
    device_s = sum(module_times(run.trace, "tiered_gather"))
    if device_s <= 0:
        return None
    least_s = run.collect_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
