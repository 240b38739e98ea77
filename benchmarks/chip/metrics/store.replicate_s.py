"""Seconds of the store's chain replication in fsync, shipping and
waiting for the acks (its ``store.replicate`` spans) per save in the
window, from the profiler trace."""
from benchmarks.chip import spans


def read(run):
    return spans.per_save(run, "store.replicate")
