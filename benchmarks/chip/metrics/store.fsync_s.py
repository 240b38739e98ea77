"""Seconds in the store's fsync (log persist and chain replication to
every replica) per save in the window, from AssiseCheckpointer.stats."""


def read(run):
    if not run.saves:
        return None
    return sum(s["stats"]["fsync_s"] for s in run.saves) / len(run.saves)
