"""Readings that the limits of ``correct`` are set from (PERF.md).

For each seed, at the cell's own sizes, in one process on the chip:

  program     the program's first three steps against the reference:
              the lower readings;
  control     the reference computed in bfloat16 in the program's place:
              the upper readings;
  half_batch  the reference in the program's place, fed the first half of
              each batch (the mean over the rest): a fault the training
              check has to catch;
  small_leaf  the program with one of its smallest leaves (bonus_u) given
              no gradient: a fault in a leaf far below the median;
  unchanged   a step that returns its state unchanged: the reference's
              losses of the first weights on each step's batch (its
              learning rate set to 0), a first moment and a change of
              nought, so ``grad_gap`` and ``update_gap`` read 1.

The control and the faults run on the first ``--fault-seeds`` seeds only
(all by default). Each line also names the program's worst leaf of each
leaf gap and every leaf's gap.

    python3 benchmarks/chip/calibrate.py --config <config> \
        --traffic <traffic> --seeds 11 12 13 [--fault-seeds 3] \
        [--out <file.jsonl>]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def zeroing_leaf(adamw_update, leaf: str):
    """``adamw_update`` with the gradient of every leaf named ``leaf``
    replaced by zeros."""
    import jax
    import jax.numpy as jnp

    def update(cfg, grads, opt_state, params):
        grads = jax.tree_util.tree_map_with_path(
            lambda p, g: jnp.zeros_like(g)
            if str(getattr(p[-1], "key", "")) == leaf else g, grads)
        return adamw_update(cfg, grads, opt_state, params)
    return update


def first_steps(job, seed: int) -> dict:
    from benchmarks.chip import weights
    from repro.optim.adamw import adamw_init
    job.seed, job.cursor, job.loss_log = seed, 0, []
    job.params = weights.make_params(job.shapes, seed)
    job.opt = adamw_init(job.params)
    job.run_first_steps(3)
    job.params = job.opt = None
    return job.first_steps


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """The program's worst leaf of each leaf gap, and every leaf's gap."""
    import numpy as np

    from benchmarks.chip import check
    keep = check.moving_leaves(ref["grad_norms"])
    out = {}
    for key, names in (("grad_norms", None), ("update_norms", keep)):
        r = ref[key]
        med = float(np.median(list(r.values())))
        names = sorted(r if names is None else names)
        out[key] = {
            "worst": check.leaf_gap(prog[key], r, names)[1],
            "gaps": {n: abs(prog[key][n] - r[n]) / max(r[n], med)
                     for n in names},
            "own": {n: abs(prog[key][n] - r[n]) / r[n] for n in names}}
    return out


def readings(job, fault_job, ref_mod, seed: int, faults: bool) -> dict:
    import jax.numpy as jnp

    from benchmarks.chip import check, weights
    prog = first_steps(job, seed)
    batches = [job._batch(i) for i in range(3)]
    cfg = job.cfg
    out = {"seed": seed}
    t = time.perf_counter()
    ref = ref_mod.train_steps(cfg, weights.make_params(job.shapes, seed),
                              batches)
    out["reference_s"] = time.perf_counter() - t
    out["program"] = check.training_numbers(prog, ref)
    out["leaves"] = leaf_gaps(prog, ref)
    out["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
    if not faults:
        return out
    faulted = first_steps(fault_job, seed)
    out["small_leaf"] = check.training_numbers(faulted, ref)
    ctl = ref_mod.train_steps(cfg, weights.make_params(job.shapes, seed),
                              batches, dtype=jnp.bfloat16)
    out["control"] = check.training_numbers(ctl, ref)
    half = [{k: v[:len(v) // 2] for k, v in b.items()} for b in batches]
    hb = ref_mod.train_steps(cfg, weights.make_params(job.shapes, seed),
                             half)
    out["half_batch"] = check.training_numbers(hb, ref)
    still = dict(cfg, optimizer=dict(cfg["optimizer"], lr=0.0))
    held = ref_mod.train_steps(still, weights.make_params(job.shapes, seed),
                               batches)
    zero = {k: 0.0 for k in ref["grad_norms"]}
    out["unchanged"] = check.training_numbers(
        {"losses": held["losses"], "grad_norms": zero,
         "update_norms": zero}, ref)
    out["losses"]["control"] = ctl["losses"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="steady")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import spec
    from benchmarks.chip.job import Job
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()
    bench = spec.Bench(ROOT)
    cfg = bench.config(args.config)
    from repro.launch import train
    traffic = bench.traffic(args.traffic)
    job = Job(cfg, traffic, args.seeds[0])
    job.build()
    job.params = job.opt = None
    fault_job = Job(cfg, traffic, args.seeds[0])
    good = train.adamw_update
    train.adamw_update = zeroing_leaf(good, "bonus_u")
    try:
        fault_job.build()
    finally:
        train.adamw_update = good
    fault_job.params = fault_job.opt = None
    ref_mod = spec.reference(cfg["reference"]["module"])
    out = open(args.out, "a") if args.out else None
    try:
        n_faults = (len(args.seeds) if args.fault_seeds is None
                    else args.fault_seeds)
        for i, seed in enumerate(args.seeds):
            line = json.dumps(readings(job, fault_job, ref_mod, seed,
                                       i < n_faults))
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
