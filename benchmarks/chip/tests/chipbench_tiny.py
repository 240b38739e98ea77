"""A copy of the benchmark at the tiny rwkv6-1.6b-reduced size, for the
CPU rehearsal tests: the same cells, metrics and checks, with the
configurations cut to the reduced widths and the traffic to 2 x 64
tokens a step."""
import json
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness, spec  # noqa: E402

TINY = dict(registry="rwkv6-1.6b-reduced", hidden_size=64, head_size=8,
            intermediate_size=64, time_mix_extra_dim=4,
            time_decay_extra_dim=8, vocab_size=512)
RUN_CONFIG = dict(rwkv_chunk=16, loss_chunk=64)  # the program's CPU sizes
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def make_root(tmp: Path) -> spec.Bench:
    """BENCHMARK.json with every configuration and traffic mix cut to
    the tiny size, under ``tmp``; the code is the repository's."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in doc["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(TINY)
        cfg["reference"] = dict(cfg["reference"], rows_per_block=1,
                                scan_chunk=16)
        cfg["checkpoint"] = dict(cfg["checkpoint"], delta_block=512)
        c["file"] = f"configs/{c['name']}.json"
        (tmp / "configs").mkdir(exist_ok=True)
        (tmp / c["file"]).write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    tdir = tmp / "benchmarks" / "chip" / "traffic"
    tdir.mkdir(parents=True)
    for f in (ROOT / "benchmarks" / "chip" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(batch=2, seq=64)
        (tdir / f.name).write_text(json.dumps(t))
    return spec.Bench(tmp)


def kernel_in_interpret_mode(monkeypatch):
    """The checkpointer's changed-block scan takes the Pallas kernel,
    interpreted, as it takes the compiled kernel on a TPU."""
    from repro.ckpt import checkpoint as C
    monkeypatch.setattr(C, "_device_scan", lambda: partial(
        C.kernel_changed_blocks, interpret=True))


def run(bench, cell, seed=2**33 + 7, seconds=0.5, trace=False):
    return harness.run_cell(bench, cell, seed, seconds, trace,
                            device=dict(DEVICE), t_start=time.perf_counter(),
                            run_config=RUN_CONFIG,
                            store_dir=str(bench.root / "store"),
                            say=lambda s: None)
