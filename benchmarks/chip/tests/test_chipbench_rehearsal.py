"""CPU rehearsal of the chip benchmark: every cell's window at the tiny
rwkv6-1.6b-reduced size, through the harness the chip runs, with the
checkpointer's kernel scan in interpret mode; the result line's keys;
and the command's refusal to run without a TPU."""
import json
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny as tiny

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench"))


@pytest.mark.parametrize("cell", ["rwkv6-1l.save-delta",
                                  "rwkv6-1l.save-full",
                                  "rwkv6-1l.failover", "rwkv6-1l.steady"])
def test_cell_window_is_correct(bench, cell, monkeypatch):
    tiny.kernel_in_interpret_mode(monkeypatch)
    result, checks = tiny.run(bench, cell)
    store = ["store"] if cell != "rwkv6-1l.steady" else []
    assert list(result) == RESULT_KEYS[:5] + store + ["checks"]
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    wanted = {m["name"] for m in bench.metrics_for(cell, "end_to_end")}
    assert set(result["metrics"]) == wanted
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert "update_gap" in checks
    if store:
        assert result["store"]["fs"] != "unknown"
    if "save" in cell:
        assert checks["readback_mismatch"][0] == 0
        assert result["metrics"]["write_amp"]["value"] > 1
    if cell == "rwkv6-1l.failover":
        assert checks["restore_mismatch"][0] == 0
        assert checks["resume_loss_gap"][0] == 0


def test_traced_run_reports_per_layer_metrics(bench, monkeypatch):
    tiny.kernel_in_interpret_mode(monkeypatch)
    result, _ = tiny.run(bench, "rwkv6-1l.failover", trace=True)
    assert list(result) == RESULT_KEYS[:5] + ["breakdown", "store",
                                              "checks"]
    assert {"window_s", "busy_s"} <= set(result["device"])
    assert {"recover.failover_s", "recover.restore_s"} == set(
        result["metrics"])


def test_traced_run_fails_where_a_listed_metric_reads_nothing(bench,
                                                              monkeypatch):
    """The CPU's trace has no TPU plane, so the device metrics that the
    save cell lists find nothing: the run fails rather than leave them
    out of its line."""
    tiny.kernel_in_interpret_mode(monkeypatch)
    with pytest.raises(RuntimeError, match="found nothing to read"):
        tiny.run(bench, "rwkv6-1l.save-delta", trace=True)


def _run_cli(cwd):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "rwkv6-1l.save-delta", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=cwd, capture_output=True, text=True,
        timeout=120, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def test_cli_refuses_without_a_tpu(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip")
    for cwd in (tiny.ROOT, tmp_path):  # the checkout, and the paths alone
        r = _run_cli(cwd)
        assert r.returncode != 0
        assert "no TPU" in r.stderr
        assert not any(line.startswith("{")
                       for line in r.stdout.splitlines()), r.stdout
