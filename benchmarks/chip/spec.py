"""BENCHMARK.json and the files it names, found by name.

  configs/<config>.json   a configuration: model sizes, optimizer,
                          checkpoint format, cluster, limits of `correct`
  traffic/<traffic>.json  a traffic mix: batch, sequence length, set-up
                          saves and the window's script of operations
  metrics/<metric>.py     one reader per metric: ``read(run)`` returns
                          the metric's value, or None where the run has
                          nothing for it to read
  archs/<name>.py         a model family: ``arch_config(cfg)`` builds
                          the program's model from a configuration file
  costs/<name>.py         operations and bytes computed from shapes
  reference/<name>.py     a configuration's plain reference
  peaks.json              published peaks by JAX device kind

A cell, a configuration, a traffic mix or a metric is added by adding
its files and its entry in BENCHMARK.json; no file here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from functools import lru_cache
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Bench:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.workloads = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def workload(self, name: str) -> dict:
        if name not in self.workloads:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(self.workloads)}")
        return self.workloads[name]

    def config(self, name: str) -> dict:
        return json.loads((self.root / self.configs[name]["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / HERE.relative_to(ROOT) / "traffic"
                           / f"{name}.json").read_text())

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The ``kind`` ("end_to_end" or "per_layer") metrics this cell
        reports: those that list it, or, without a list, every cell that
        reports the end-to-end metric they move (end-to-end metrics
        without a list: every cell)."""
        e2e = [m["name"] for m in self.doc["end_to_end"]
               if cell in m.get("workloads", [cell])]
        out = []
        for m in self.doc[kind]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out


@lru_cache(maxsize=None)
def _load_file(path: str):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + Path(path).stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _load_file(str(HERE / "metrics" / f"{name}.py")).read


def arch(name: str):
    return _load_file(str(HERE / "archs" / f"{name}.py"))


def cost(name: str):
    return _load_file(str(HERE / "costs" / f"{name}.py"))


def reference(name: str):
    return importlib.import_module(f"benchmarks.chip.reference.{name}")


def peaks(kind: str) -> Dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} has no peaks in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]
