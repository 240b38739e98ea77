"""Records ``data/tpu_window.xplane.pb`` on a TPU: the profiler trace of
a window of two steps and one save at the tiny chipbench sizes, after a
set-up save, so that the window's save scans its leaves with the
compiled delta_mask kernel. The file keeps what the readers read: the
HLO modules (the ``/host:metadata`` plane) and the ops' source stacks
are dropped. It then prints what the trace holds.

    python3 benchmarks/chip/tests/record_tpu_window.py [OUT]
"""
import json
import sys
import tempfile
import time
from pathlib import Path

import chipbench_tiny as tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import harness, spans, spec, trace

CELL = "tiny.record"
TRAFFIC = {"batch": 2, "seq": 64, "setup_saves": 1,
           "window": [{"op": "steps", "n": 2}, {"op": "save"}]}


def record(out: Path, tmp: Path) -> dict:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's devices are {dev.platform}")
    bench = tiny.make_root(tmp)
    (tmp / "benchmarks" / "chip" / "traffic" / "record.json").write_text(
        json.dumps(TRAFFIC))
    doc = json.loads((tmp / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": CELL,
                             "config": doc["configs"][0]["name"],
                             "traffic": "record", "chips": 1, "why": "-"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(tmp)
    load = trace.load

    def keep(path):  # the harness's reader, keeping a copy of the file
        trim(trace.find_xplane(path), out)
        return load(path)

    trace.load = keep
    try:
        result, _ = harness.run_cell(
            bench, CELL, 2**33 + 7, 0.0, True,
            device={"platform": dev.platform, "kind": dev.device_kind,
                    "count": 1},
            t_start=time.perf_counter(), run_config=tiny.RUN_CONFIG,
            store_dir=str(tmp / "store"), say=print)
    finally:
        trace.load = load
    return result


def trim(src: str, out: Path) -> None:
    space = spans.xplane_pb2().XSpace()
    space.ParseFromString(Path(src).read_bytes())
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    for plane in space.planes:
        drop = {k for k, m in plane.stat_metadata.items()
                if m.name == "source_stack"}
        for md in plane.event_metadata.values():
            kept = [st for st in md.stats if st.metadata_id not in drop]
            del md.stats[:]
            md.stats.extend(kept)
    out.write_bytes(space.SerializeToString())


def describe(path: Path) -> None:
    t, pt = trace.load(str(path)), spans.load(str(path))
    print(f"{path}: {path.stat().st_size} bytes; window {t.window_s:.4f} s;"
          f" chips {sorted(t.device_ops)}; busy {t.busy_s:.4f} s")
    print("program spans:", sorted({ev.name for ev in pt.program_spans}))
    print("wkv device seconds:", pt.scope_seconds("wkv"),
          "step runs:", t.module_runs("step_fn"))
    for scope in sorted({p for p, _, _ in pt.scoped_ops.get(0, [])})[:20]:
        print("op scope:", scope)


if __name__ == "__main__":
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else \
        Path(__file__).parent / "data" / "tpu_window.xplane.pb"
    with tempfile.TemporaryDirectory() as tmp:
        res = record(out, Path(tmp))
    print(json.dumps(res))
    describe(out)
