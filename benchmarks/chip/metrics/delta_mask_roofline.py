"""The delta_mask kernel's share of its HBM roofline (percent): the bytes
it had to move for the window's saves (costs/delta_mask.py) at the
chip's peak bandwidth, over the device time of its events in the
trace."""
from benchmarks.chip import spec

KERNEL = "delta_mask"


def read(run):
    if run.trace is None:
        return None
    scanned = sum(s["stats"]["kernel_bytes"] for s in run.saves)
    secs = sum(t for name, t in run.trace.op_seconds().items()
               if KERNEL in name)
    if not scanned or not secs:
        return None
    block = run.cfg["checkpoint"]["delta_block"]
    moved = spec.cost("delta_mask").bytes_moved(scanned, block)
    return 100.0 * moved / run.peak["hbm_bytes_per_s"] / secs
