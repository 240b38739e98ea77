"""Seconds in restore() and the upload of the restored state (host
clock)."""


def read(run):
    return run.recoveries[0]["restore_s"] if run.recoveries else None
