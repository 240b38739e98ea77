"""Seconds in failure detection and failover_process (host clock)."""


def read(run):
    return run.recoveries[0]["failover_s"] if run.recoveries else None
