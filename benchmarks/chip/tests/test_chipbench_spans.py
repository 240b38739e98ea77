"""The program's spans in a profiler trace: recorded on the CPU around a
real save, failover and restore; the readers of the per-layer metrics
that read them, on synthetic traces; and a trace recorded on a TPU."""
import types
from pathlib import Path

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import spans, spec, trace

DATA = Path(__file__).parent / "data"
MS = 1_000_000

# span -> (the span it sits in on its own thread, counts it carries)
TABLE = {
    "ckpt.save": (None, {"step", "leaves"}),
    "ckpt.d2h": ("ckpt.save", {"nbytes"}),
    "ckpt.encode": ("ckpt.save", {"nbytes"}),
    "ckpt.scan": ("ckpt.save", {"kernel_bytes", "host_bytes"}),
    "store.append": ("ckpt.save", {"nbytes"}),
    "store.digest": ("ckpt.save", {"nbytes"}),
    "store.persist": ("ckpt.save", {"nbytes"}),
    "store.replicate": ("ckpt.save", {"nbytes", "entries"}),
    "repl.hop": ("store.replicate", {"node", "nbytes"}),
    "store.digest_apply": (None, {"node", "nbytes"}),
    "cluster.failover": (None, set()),
    "ckpt.restore": (None, {"leaves"}),
    "ckpt.read": ("ckpt.restore", {"nbytes"}),
    "ckpt.decode": ("ckpt.restore", {"nbytes"}),
}


def _parent(pt, ev):
    """The innermost program span around ``ev`` on its thread."""
    around = [p for p in pt.program_spans if p is not ev
              and p.thread == ev.thread and p.start <= ev.start
              and ev.end <= p.end]
    return min(around, key=lambda p: p.end - p.start, default=None)


def test_spans_of_a_save_failover_and_restore_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.ckpt import AssiseCheckpointer, CheckpointConfig
    from repro.core import AssiseCluster
    rng = np.random.default_rng(0)
    state = {f"w{i}": jnp.asarray(rng.standard_normal(4096, np.float32))
             for i in range(6)}
    cl = AssiseCluster(str(tmp_path / "c"), n_nodes=3, replication=2,
                       n_reserve=1, log_capacity=96 << 10)
    cfg = CheckpointConfig(delta=True, delta_block=512)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            store = cl.open_process("p")
            ck = AssiseCheckpointer(store, cfg)
            ck.save(0, state)
            state["w0"] = state["w0"].at[7].add(1.0)
            ck.save(1, state)
            store.drain()
            node = store.sfs.node_id
            cl.kill_process(store)
            cl.kill_node(node)
            cl.detect_failures_now()
            store = cl.failover_process("p")
            flat, man = AssiseCheckpointer(store, cfg).restore()
    finally:
        jax.profiler.stop_trace()
        cl.close()
    assert man["step"] == 1
    np.testing.assert_array_equal(flat["/w0"], np.asarray(state["w0"]))
    pt = spans.load(str(tmp_path / "prof"))
    names = {ev.name for ev in pt.program_spans}
    assert {spans.PREFIX + n for n in TABLE} <= names
    saving = pt.thread_of("ckpt.save")
    for name, (parent, counts) in TABLE.items():
        evs = pt.spans(name)
        assert all(counts <= set(ev.stats) for ev in evs), name
        if parent is None:
            continue
        here = [ev for ev in evs if ev.thread == (
            saving if parent != "ckpt.restore"
            else pt.thread_of("ckpt.restore"))]
        assert here, name
        for ev in here:
            p = _parent(pt, ev)
            # a hop forwards down the chain inside the previous hop
            assert p is not None and p.name in (
                spans.PREFIX + parent, "assise.repl.hop"), (name, p)
    assert {ev.stats["node"] for ev in pt.spans("repl.hop")} == {
        "node1", "node2"}
    assert any(ev.thread != saving for ev in pt.spans("store.digest_apply"))
    assert sum(ev.stats["leaves"] for ev in pt.spans("ckpt.save")) == 12
    assert [ev.stats["leaves"] for ev in pt.spans("ckpt.restore")] == [6]
    assert sum(ev.stats["nbytes"] for ev in pt.spans("ckpt.d2h")) \
        == 12 * 4096 * 4
    # a save's own time, outside every span inside it, is small
    save_s = pt.span_seconds("ckpt.save")
    assert 0 < pt.self_seconds("ckpt.save") < save_s


# -- the metric readers, on synthetic traces ---------------------------------

def _ev(name, start, end, thread=0, **stats):
    return spans.HostEvent(spans.PREFIX + name, start * MS, end * MS,
                           thread, stats)


def _run(pt, saves=1, recoveries=0, trace_=True):
    w0 = pt.window[0]
    t = trace.Trace(window=pt.window,
                    device_ops={0: [("op", w0, w0 + 1)]},
                    modules=[("jit_step_fn(1)", w0, w0 + 5 * MS),
                             ("jit_step_fn(2)", w0 + 50 * MS,
                              w0 + 60 * MS)],
                    host_spans=[]) if trace_ else None
    return types.SimpleNamespace(trace=t, program_trace=pt,
                                 saves=[{}] * saves,
                                 recoveries=[{}] * recoveries)


def _program():
    """A window of 100 ms (from 1,000 ms on): a save on thread 0 with a
    child of each kind, store spans on a digest thread (1), a restore;
    ops of the step inside and outside the ``wkv`` scope on chip 0."""
    w0 = 1000
    evs = [_ev("ckpt.save", w0 + 10, w0 + 60),
           _ev("ckpt.d2h", w0 + 10, w0 + 12), _ev("ckpt.d2h", w0 + 20,
                                                  w0 + 23),
           _ev("ckpt.scan", w0 + 12, w0 + 15),
           _ev("store.append", w0 + 15, w0 + 19),
           _ev("store.digest", w0 + 23, w0 + 30),
           _ev("store.persist", w0 + 30, w0 + 31),
           _ev("store.replicate", w0 + 31, w0 + 59),
           _ev("repl.hop", w0 + 32, w0 + 58, node="node1"),
           _ev("store.digest", w0 + 40, w0 + 48, thread=1),
           _ev("store.append", w0 + 41, w0 + 44, thread=1),
           _ev("store.persist", w0 + 61, w0 + 63, thread=1),
           _ev("ckpt.restore", w0 + 70, w0 + 90),
           _ev("ckpt.read", w0 + 70, w0 + 80),
           _ev("ckpt.read", w0 + 80, w0 + 85),
           _ev("ckpt.decode", w0 + 85, w0 + 89),
           # outside the window: cut away
           _ev("ckpt.d2h", w0 + 99, w0 + 120)]
    wkv = [("jit(step_fn)/jit(main)/wkv/while", w0 * MS, (w0 + 4) * MS),
           ("jit(step_fn)/jit(main)/wkv/while/body/dot", (w0 + 1) * MS,
            (w0 + 2) * MS),
           ("jit(step_fn)/transpose(jvp(wkv))/while", (w0 + 50) * MS,
            (w0 + 56) * MS),
           ("jit(step_fn)/jit(main)/wkv_norm/mul", (w0 + 5) * MS,
            (w0 + 9) * MS),
           ("jit(step_fn)/channel_mix/dot", (w0 + 56) * MS,
            (w0 + 60) * MS)]
    return spans.ProgramTrace(window=(w0 * MS, (w0 + 100) * MS),
                              program_spans=evs, scoped_ops={0: wkv})


EXPECTED = {  # metric: (value per save or per recovery, saves, recoveries)
    "ckpt.d2h_s": (0.006, 1, 0),  # 2 + 3 + 1 (cut) ms
    "ckpt.scan_s": (0.0015, 2, 0),
    "store.append_s": (0.004, 1, 0),  # the saving thread only
    "store.digest_s": (0.007, 1, 0),
    "store.persist_s": (0.0015, 2, 0),  # every thread
    "store.replicate_s": (0.028, 1, 0),
    "train_step.wkv_s": (0.005, 0, 0),  # (4 + 6) ms over 2 step runs
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_metric_reads_the_program_trace(metric):
    value, saves, recs = EXPECTED[metric]
    read = spec.metric_reader(metric)
    assert read(_run(_program(), saves, recs)) == pytest.approx(value)
    assert read(_run(_program(), saves, recs, trace_=False)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_metric_reads_zero_from_a_program_without_spans(metric):
    """A program that emits no spans and names no scope (the one before
    them) reads 0 in the cells the metric lists, rather than nothing,
    which would fail the run."""
    value, saves, recs = EXPECTED[metric]
    bare = spans.ProgramTrace(window=(5 * MS, 105 * MS), program_spans=[])
    assert spec.metric_reader(metric)(_run(bare, saves, recs)) == 0.0


def test_metrics_per_save_need_a_save():
    read = spec.metric_reader("store.persist_s")
    assert read(_run(_program(), saves=0)) is None


def test_self_time_and_gap_spans():
    pt = _program()
    # ckpt.save (50 ms) less its children on thread 0: d2h 2 + 3, scan 3,
    # append 4, digest 7, persist 1, replicate 28
    assert pt.self_seconds("ckpt.save") == pytest.approx(0.002)
    assert pt.self_seconds("store.replicate") == pytest.approx(0.002)
    assert pt.self_seconds("repl.hop") == pytest.approx(0.026)
    t = trace.Trace(window=pt.window,
                    device_ops={0: [("fusion", 1000 * MS, 1031 * MS),
                                    ("fusion", 1059 * MS, 1100 * MS)]},
                    modules=[],
                    host_spans=[("bench.save", 1010 * MS, 1060 * MS)])
    pt.host_events = [spans.HostEvent("PjRt::Execute", 1040 * MS,
                                      1045 * MS, 2),
                      spans.HostEvent("bench.save", 1010 * MS, 1060 * MS,
                                      0)]
    (gap,) = pt.gap_spans(t)
    assert gap["at_s"] == pytest.approx(0.031)
    assert gap["gap_s"] == pytest.approx(0.028)
    assert gap["spans"][0] == ["assise.repl.hop", pytest.approx(0.026)]
    assert {n for n, _ in gap["spans"]} == {
        "assise.repl.hop", "assise.store.replicate", "assise.store.append",
        "assise.store.digest"}
    assert gap["runtime"] == [["PjRt::Execute", pytest.approx(0.005)]]


def test_of_finds_the_runs_file_by_its_window(tmp_path):
    """``of`` finds the harness's trace directory by the run's window,
    passing over a directory whose trace is another run's."""
    import shutil
    for d, f in (("chipbench-trace-a", "cpu_window.xplane.pb"),
                 ("chipbench-trace-b", "tpu_window.xplane.pb")):
        (tmp_path / d / "plugins").mkdir(parents=True)
        shutil.copy(DATA / f, tmp_path / d / "plugins" / "h.xplane.pb")
    want = trace.load(str(DATA / "tpu_window.xplane.pb")).window
    pt = spans.find(want, str(tmp_path))
    assert pt is not None and pt.window == want
    assert spans.find((0, 1), str(tmp_path)) is None


def test_recorded_tpu_trace():
    """A window recorded on a TPU v5 lite at the tiny sizes: two steps
    and a save whose leaves the delta_mask kernel scanned. The program's
    spans, the device plane and the ``wkv`` scope on its op events are
    all there."""
    path = str(DATA / "tpu_window.xplane.pb")
    t, pt = trace.load(path), spans.load(path)
    assert t.device_ops and t.busy_s > 0
    assert t.module_runs("step_fn")[0] == 2
    names = {ev.name for ev in pt.program_spans}
    assert {"assise.ckpt.save", "assise.ckpt.d2h", "assise.ckpt.encode",
            "assise.ckpt.scan", "assise.store.append",
            "assise.store.persist", "assise.store.replicate",
            "assise.repl.hop"} <= names
    (save,) = pt.spans("ckpt.save")
    assert save.stats["leaves"] == 85
    assert any("delta_mask" in n for n in t.op_seconds())
    assert 0 < pt.scope_seconds("wkv") < t.busy_s
