"""Seconds of the store's log appends on the saving thread (its
``store.append`` spans in put and write) per save in the window, from
the profiler trace."""
from benchmarks.chip import spans


def read(run):
    return spans.per_save(run, "store.append", on_saving_thread=True)
