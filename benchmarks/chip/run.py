"""Chip benchmark: one cell of BENCHMARK.json, run on this machine's chips.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. With ``--trace 0`` the result line
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window. Earlier lines say
what set-up cost, what the window did and wrote, and each number that
decides ``correct`` beside its limit (also the last lines on standard
error). The last line of standard output is the result, one JSON object.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int):
    """The device description for the result line, or None (with the
    reason on standard error) where this machine cannot run the cell."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"run.py: no TPU: JAX found no backend ({e})", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"run.py: no TPU: JAX's devices are {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness, spec
    bench = spec.Bench(ROOT)
    cell = bench.workload(args.workload)
    device = find_chips(cell["chips"])
    if device is None:
        return 2
    spec.peaks(device["kind"])  # an unknown device kind is an error
    print(f"device: {device['platform']} {device['kind']} x "
          f"{device['count']}", flush=True)
    from repro.launch.train import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    result, checks = harness.run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        device=device, t_start=T_START,
        say=lambda s: print(s, flush=True))
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
