"""Seconds the checkpointer spent copying leaves from the device to
the host (its ``ckpt.d2h`` spans) per save in the window, from the
profiler trace."""
from benchmarks.chip import spans


def read(run):
    return spans.per_save(run, "ckpt.d2h")
