"""Seconds from the kill of the worker and its node to the first resumed
step completed on state restored from the replica: detection, failover,
restore, upload and that step."""


def read(run):
    return run.recoveries[0]["recover_s"] if run.recoveries else None
