"""The held experts' grouped products against the chip's bf16 peak
(percent): the operations a step's routing is built to give them
(``expert_flops_per_step`` of the configuration's cost module), over the
device seconds per run of the jitted train step of the ops under the
``moe_experts`` scope and of the grouped-product kernels that XLA takes
out of it (``grouped.py``), over the peak. A program that does not name
the scope and runs no such kernel reads 0."""
from benchmarks.chip import grouped, spans, spec

STEP_PROGRAM = "step_fn"
SCOPE = "moe_experts"


def read(run):
    pt = spans.of(run)
    if pt is None:
        return None
    runs, _ = run.trace.module_runs(STEP_PROGRAM)
    if not runs:
        return None
    secs = grouped.scope_seconds(run, SCOPE) / runs
    if not secs:
        return 0.0
    flops = spec.cost(run.cfg["cost"]).expert_flops_per_step(
        run.cfg, run.job.tokens_per_step)
    return 100.0 * flops / secs / run.peak["bf16_flops_per_s"]
