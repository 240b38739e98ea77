"""BENCHMARK.json and the files it names: every configuration, traffic
mix, metric reader and cost function loads by name, and each cell has
what the result line needs."""
import json

import pytest

import chipbench_tiny as tiny
from benchmarks.chip import spec

BENCH = spec.Bench(tiny.ROOT)
DOC = BENCH.doc


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert DOC["paths"] == ["benchmarks/chip"]


@pytest.mark.parametrize("name", sorted(BENCH.configs))
def test_config_loads_by_name(name):
    cfg = BENCH.config(name)
    assert cfg["name"] == name
    assert cfg["source"] == BENCH.configs[name]["source"]
    assert spec.reference(cfg["reference"]["module"]).train_steps
    assert spec.arch(cfg["arch"]).arch_config
    assert spec.cost(cfg["cost"]).flops_per_token(cfg) > 0
    published = cfg["published"]
    assert set(BENCH.configs[name]["reduced"]) == set(published)
    assert all(cfg[k] != v for k, v in published.items())


@pytest.mark.parametrize("cell", sorted(BENCH.workloads))
def test_cell_loads_and_reports(cell):
    w = BENCH.workload(cell)
    assert w["config"] in BENCH.configs and w["chips"] == 1
    traffic = BENCH.traffic(w["traffic"])
    assert traffic["batch"] > 0 and traffic["window"]
    e2e = [m["name"] for m in BENCH.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert BENCH.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["end_to_end"]
                                    + DOC["per_layer"]])
def test_metric_reader_loads_by_name(metric):
    assert callable(spec.metric_reader(metric))


def test_peaks_by_device_kind():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_layers_are_named_alike():
    layers = {}
    for m in DOC["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) == {"device", "model step", "checkpointer",
                           "delta-scan kernel", "store", "recovery"}
    assert json.dumps(DOC).count("\t") == 0
