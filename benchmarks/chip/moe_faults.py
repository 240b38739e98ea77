"""Readings of faults planted in the held-expert layer (PERF.md section 2).

For each seed, at the cell's own sizes, in one process on the chip: the
program's first three steps, and those steps with one fault planted in
the gates of ``repro.models.moe``, against the reference, read as
``correct`` reads them.

  program   no fault: the lower readings;
  capacity  each sequence's assignments to an expert past a capacity of
            int(S k 1.25 / E) + 1, in token order, get a gate of 0: the
            drop of a dispatch into static per-expert buffers at the
            capacity factor 1.25;
  renorm    the top-k gates renormalised to sum to 1, which
            DeepSeek-V2-Lite (``norm_topk_prob`` false) does not do.

Each line also gives the share of the held experts' assignments that the
capacity fault drops in the first step, counted on the reference's
routing of the first batch.

    python3 benchmarks/chip/moe_faults.py --config <config> \
        --traffic <traffic> --seeds 11 12 [--out <file.jsonl>]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FACTOR = 1.25


def capacity(seq: int, n_experts: int, k: int) -> int:
    return int(seq * k * FACTOR / n_experts) + 1


def capacity_gates(gates, seq: int):
    """``gates`` with each sequence's assignments past the capacity
    zeroed (probs: (B S, E), one sequence after another)."""
    import jax
    import jax.numpy as jnp

    def planted(probs, spec):
        top_p, top_i = gates(probs, spec)
        t, k = top_i.shape
        ids = top_i.reshape(t // seq, seq * k)
        onehot = jax.nn.one_hot(ids, spec.n_experts, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, 1) - onehot,
                                  ids[..., None], -1)[..., 0]
        keep = pos < capacity(seq, spec.n_experts, k)
        return jnp.where(keep.reshape(t, k), top_p, 0), top_i
    return planted


def renorm_gates(gates):
    def planted(probs, spec):
        return gates(probs, dataclasses.replace(spec, norm_topk=True))
    return planted


def dropped_share(top_sets, first: int, held: int, n_experts: int) -> float:
    """Share of the held experts' assignments past the capacity, over
    the MoE layers' top-k ids (each (B, S, k))."""
    import numpy as np
    dropped = total = 0
    for ids in top_sets:
        b, s, k = ids.shape
        cap = capacity(s, n_experts, k)
        for e in range(first, first + held):
            count = (ids == e).reshape(b, s * k).sum(1)
            dropped += int(np.maximum(count - cap, 0).sum())
            total += int(count.sum())
    return dropped / max(total, 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import check, spec, weights
    from benchmarks.chip.calibrate import first_steps
    from benchmarks.chip.job import Job
    from repro.launch.train import enable_compile_cache
    from repro.models import moe
    enable_compile_cache()
    bench = spec.Bench(ROOT)
    cfg = bench.config(args.config)
    traffic = bench.traffic(args.traffic)
    ref_mod = spec.reference(cfg["reference"]["module"])
    good = moe._gates
    jobs = {}
    for name, planted in (("program", good),
                          ("capacity", capacity_gates(good, traffic["seq"])),
                          ("renorm", renorm_gates(good))):
        moe._gates = planted
        try:
            jobs[name] = Job(cfg, traffic, args.seeds[0])
            jobs[name].build()
        finally:
            moe._gates = good
        jobs[name].params = jobs[name].opt = None
    out = open(args.out, "a") if args.out else None
    try:
        for seed in args.seeds:
            job = jobs["program"]
            job.seed = seed
            batches = [job._batch(i) for i in range(3)]
            params = weights.make_params(job.shapes, seed)
            sets = ref_mod.top_sets(params, batches[0]["tokens"], cfg)
            line = {"seed": seed, "capacity_dropped_share": dropped_share(
                sets, 0, cfg["n_routed_experts"],
                cfg["published"]["n_routed_experts"])}
            ref = ref_mod.train_steps(cfg, params, batches)
            for name, fjob in jobs.items():
                line[name] = check.training_numbers(first_steps(fjob, seed),
                                                    ref)
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
