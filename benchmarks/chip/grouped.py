"""Device seconds of a ``jax.named_scope`` with the grouped matrix
products made under it.

On a TPU, XLA replaces each ``jax.lax.ragged_dot`` by a Mosaic kernel
(a ``tpu_custom_call``) whose metadata names it ``ragged-dot-none``, and
its group table ``ragged-dot-metadata``, in place of the scope path of
the code that made it. A scope read from the ops' ``tf_op`` stat
(``spans.ProgramTrace.scope_seconds``) therefore leaves those kernels
out. In this program only the MoE layer's held experts run ragged dots,
inside the ``moe_experts`` scope, so their events, found by name on the
``XLA Ops`` line, are added to that scope and to ``moe`` around it.
"""
from __future__ import annotations

import re

from benchmarks.chip import spans

KERNEL = re.compile(r"%ragged-dot-[\w.-]* = ")


def scope_seconds(run, scope: str, chip: int = 0) -> float:
    """Device seconds inside the run's window in which an op under
    ``scope`` or a ragged-dot kernel ran on ``chip`` (the union of their
    intervals). The run must have a program trace."""
    pt = spans.of(run)
    word = re.compile(r"(?<![\w.-])" + re.escape(scope) + r"(?![\w.-])")
    ivs = [pt._clip(s, e) for path, s, e in pt.scoped_ops.get(chip, ())
           if word.search(path)]
    ivs += [pt._clip(s, e) for name, s, e in
            run.trace.device_ops.get(chip, ()) if KERNEL.match(name)]
    return sum(e - s for s, e in spans._union(ivs)) / 1e9
