"""Seconds the training loop spent in save() and wait(), per save in the
window (host clock)."""


def read(run):
    if not run.saves:
        return None
    return sum(s["stall_s"] for s in run.saves) / len(run.saves)
