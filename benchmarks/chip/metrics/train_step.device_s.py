"""Device seconds of one run of the jitted train step, averaged over the
window's runs, from the profiler trace."""

STEP_PROGRAM = "step_fn"


def read(run):
    if run.trace is None:
        return None
    runs, secs = run.trace.module_runs(STEP_PROGRAM)
    return secs / runs if runs else None
