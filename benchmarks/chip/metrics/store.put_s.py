"""Seconds in the store's put/write calls (log append, chain hand-off,
threshold digests) per save in the window, from AssiseCheckpointer.stats."""


def read(run):
    if not run.saves:
        return None
    return sum(s["stats"]["put_s"] for s in run.saves) / len(run.saves)
