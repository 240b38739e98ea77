"""Device seconds of the ops made under the ``moe`` scope (the expert
layer whole: routing, sort, held experts, shared experts, combine;
forward and backward), with the held experts' grouped-product kernels
that XLA takes out of the scope (``grouped.py``), per run of the jitted
train step, from the profiler trace. A program that does not name the
scope and runs no such kernel reads 0."""
from benchmarks.chip import grouped, spans

STEP_PROGRAM = "step_fn"
SCOPE = "moe"


def read(run):
    pt = spans.of(run)
    if pt is None:
        return None
    runs, _ = run.trace.module_runs(STEP_PROGRAM)
    return grouped.scope_seconds(run, SCOPE) / runs if runs else None
