"""The whole window's model FLOP utilization (percent): operations the
forward and backward passes require per token (the module under costs/
that the configuration names as its ``cost``), times the tokens of the
window's steps, over the window and the chips' bf16 peak."""
from benchmarks.chip import spec


def read(run):
    if not run.steps:
        return None
    flops = spec.cost(run.cfg["cost"]).flops_per_token(run.cfg) * run.tokens
    return 100.0 * flops / run.window_s / (
        run.chips * run.peak["bf16_flops_per_s"])
