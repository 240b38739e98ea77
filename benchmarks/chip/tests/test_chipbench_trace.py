"""The trace reduction: busy and idle time, op and program seconds and
idle gaps named by the host span that overlaps them."""
from pathlib import Path

import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import trace

DATA = Path(__file__).parent / "data"


def _synthetic():
    ms = 1_000_000
    return trace.Trace(
        window=(0, 100 * ms),
        device_ops={0: [("fusion.1", -5 * ms, 10 * ms),
                        ("fusion.2", 8 * ms, 20 * ms),
                        ("delta_mask", 60 * ms, 70 * ms),
                        ("fusion.1", 95 * ms, 120 * ms)]},
        modules=[("jit_step_fn(1)", -5 * ms, 20 * ms),
                 ("jit_step_fn(2)", 95 * ms, 120 * ms)],
        host_spans=[("bench.step", 0, 20 * ms),
                    ("bench.save", 20 * ms, 90 * ms),
                    ("bench.step", 90 * ms, 120 * ms)])


def test_busy_and_idle_inside_the_window():
    t = _synthetic()
    assert t.window_s == pytest.approx(0.1)
    # [0, 20) + [60, 70) + [95, 100) ms: ops outside the window are cut
    assert t.busy_s == pytest.approx(0.035)
    ops = t.op_seconds()
    assert ops["fusion.1"] == pytest.approx(0.015)
    assert ops["delta_mask"] == pytest.approx(0.010)
    assert t.module_runs("step_fn") == (2, pytest.approx(0.025))


def test_idle_gaps_are_named_by_the_host_span():
    gaps = _synthetic().idle_gaps()
    # idle [20, 60) and [70, 95) ms, longest first
    assert [n for n, _ in gaps] == ["bench.save", "bench.save"]
    assert [s for _, s in gaps] == pytest.approx([0.04, 0.025])


def test_recorded_cpu_trace():
    """A trace recorded by the CPU backend: three bench.step and three
    bench.save spans inside bench.window, and no TPU plane."""
    t = trace.load(str(DATA / "cpu_window.xplane.pb"))
    assert 0.03 < t.window_s < 1.0
    assert [n for n, _, _ in t.host_spans] == ["bench.step",
                                                "bench.save"] * 3
    assert all(t.window[0] <= s and e <= t.window[1]
               for _, s, e in t.host_spans)
    assert t.busy_s == 0.0 and t.idle_gaps() == []
