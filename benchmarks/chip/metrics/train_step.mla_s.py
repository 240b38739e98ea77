"""Device seconds of the ops made under the ``mla`` scope (latent
attention, every layer, forward and backward) per run of the jitted
train step, from the profiler trace. A program that does not name the
scope reads 0."""
from benchmarks.chip import spans

STEP_PROGRAM = "step_fn"
SCOPE = "mla"


def read(run):
    pt = spans.of(run)
    if pt is None:
        return None
    runs, _ = run.trace.module_runs(STEP_PROGRAM)
    return pt.scope_seconds(SCOPE) / runs if runs else None
