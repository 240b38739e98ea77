"""Share of the window in which no operation ran on the chip, from the
profiler trace (percent)."""


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
