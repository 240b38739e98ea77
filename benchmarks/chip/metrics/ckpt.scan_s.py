"""Seconds the checkpointer spent finding each leaf's changed blocks
(its ``ckpt.scan`` spans: the delta_mask kernel and the host scan) per
save in the window, from the profiler trace."""
from benchmarks.chip import spans


def read(run):
    return spans.per_save(run, "ckpt.scan")
