"""Bytes the process wrote from the window's start until the store had
drained (background digests included) per byte of encoded state that
the window's saves covered."""


def read(run):
    full = sum(s["stats"]["bytes_full"] for s in run.saves)
    if not full or run.window_write_bytes is None:
        return None
    return run.window_write_bytes / full
