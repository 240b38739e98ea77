"""Seconds of the store's log flushes to the persistence domain in
fsync (its ``store.persist`` spans) per save in the window, from the
profiler trace."""
from benchmarks.chip import spans


def read(run):
    return spans.per_save(run, "store.persist")
