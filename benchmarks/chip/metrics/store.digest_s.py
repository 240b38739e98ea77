"""Seconds the saving thread spent in the store's threshold digests
(its ``store.digest`` spans: the wait for the previous digest, the seal
and its persist) per save in the window, from the profiler trace."""
from benchmarks.chip import spans


def read(run):
    return spans.per_save(run, "store.digest", on_saving_thread=True)
