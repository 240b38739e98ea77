"""Cluster-wide observability (PR 10): op-granular tracing through the
write/read/fail-over pipelines, the unified per-node metrics registry,
and the crash-surviving flight recorder — plus the transport accounting
fixes that rode along (exact dup-path wire bytes, the single modeled-
wire formula)."""
import json

import pytest

from benchmarks.common import modeled_us
from repro.core import AssiseCluster, Fault, NodeDown, RpcTimeout
from repro.core.obs import (FlightRecorder, Histogram, MetricsRegistry,
                            Tracer)
from repro.core.transport import (NET_BW_BPS, NET_LAT_READ_S,
                                  NET_LAT_WRITE_S, Transport,
                                  TransportStats, modeled_wire_s)


def make(tmp_path, **kw):
    kw.setdefault("n_nodes", 3)
    kw.setdefault("replication", 2)
    kw.setdefault("trace_sampling", 1.0)  # tests trace every op
    return AssiseCluster(str(tmp_path / "c"), **kw)


# -- metrics registry ---------------------------------------------------------

def test_histogram_log2_percentiles_without_samples():
    h = Histogram()
    for v in (1, 2, 3, 100, 1000):
        h.observe(v)
    assert h.n == 5
    # percentile reports the bucket's upper bound: within 2x above
    assert 100 <= h.percentile(0.8) <= 200
    assert 1000 <= h.percentile(0.99) <= 2000
    d = h.to_dict()
    assert d["count"] == 5 and d["p50"] >= 3
    assert sum(d["buckets"].values()) == 5


def test_histogram_percentiles_are_upper_bounds():
    h = Histogram()
    for _ in range(100):
        h.observe(17.3)
    for p in (0.5, 0.99, 0.999):
        assert 17.3 <= h.percentile(p) <= 2 * 17.3


def test_scoped_counters_publish_into_the_registry_dump():
    reg = MetricsRegistry("n")
    stats = reg.scoped("x.", seed=("a", "b"))
    stats["a"] += 3
    stats["c"] = 7  # unseeded keys work too
    assert stats["a"] == 3 and stats["b"] == 0 and stats["c"] == 7
    assert stats.get("never", 0) == 0
    assert stats["never"] == 0  # counters are born zero
    dumped = reg.to_dict()["counters"]
    assert dumped["x.a"] == 3 and dumped["x.c"] == 7
    assert dict(stats) == {"a": 3, "b": 0, "c": 7}


def test_registry_dump_is_json_serializable():
    reg = MetricsRegistry("n")
    reg.inc("ops", 5)
    reg.gauge("depth", 3)
    reg.observe("lat.us", 12.5)
    d = json.loads(json.dumps(reg.to_dict()))
    assert d["counters"]["ops"] == 5
    assert d["histograms"]["lat.us"]["count"] == 1


def test_transport_stats_attributes_are_registry_counters():
    t = Transport()
    t.stats.retries += 2
    assert t.stats.retries == 2
    assert t.metrics.counters["wire.retries"] == 2
    assert t.stats.rpcs == t.metrics.counters["wire.rpcs"] == 0


def test_cluster_metrics_dump_covers_every_registry(tmp_path):
    c = make(tmp_path)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/m/x", b"v" * 128)
        ls.fsync()
        ls.digest()
        dump = json.loads(json.dumps(c.metrics_dump()))
        assert dump["node0"]["counters"]["proc.p.puts"] == 1
        assert dump["node0"]["counters"]["sharedfs.digests"] >= 1
        assert dump["transport"]["counters"]["wire.rpcs"] >= 1
        assert dump["cm"]["counters"].get("cm.heartbeats", 0) >= 0
        # op latency histograms live in the node registry
        assert dump["node0"]["histograms"]["op.put.us"]["count"] == 1
    finally:
        c.close()


# -- satellite: exact wire accounting on the duplicate path -------------------

class _Echo:
    def ping(self, data):
        return b"pong"


def _raw_transport():
    t = Transport()
    t.register_endpoint("dst", _Echo())
    return t


def test_rpc_accounting_baseline_exact_bytes():
    t = _raw_transport()
    payload = b"x" * 100
    with t.act_as("src"):
        assert t.rpc("dst", "ping", payload) == b"pong"
    # one request (payload + 64B header) + the 4B response
    assert t.stats.rpcs == 1
    assert t.stats.bytes_sent == (100 + 64) + 4
    assert t.stats.rpc_resp_bytes == 4
    assert t.stats.retrans_rpcs == 0 and t.stats.retrans_bytes == 0


def test_rpc_dup_charges_exactly_one_retransmission():
    """Regression: the dup path used to hand-roll its accounting; it
    must charge exactly one extra request crossing the wire, tallied
    under retrans_* so unique traffic stays separable."""
    t = _raw_transport()
    from repro.core.faults import FaultInjector
    t.install_faults(FaultInjector([Fault("dup", op="rpc", count=1)]))
    payload = b"x" * 100
    with t.act_as("src"):
        assert t.rpc("dst", "ping", payload) == b"pong"
    assert t.stats.rpcs == 2                       # receiver saw it twice
    assert t.stats.bytes_sent == 2 * (100 + 64) + 4  # one response only
    assert t.stats.retrans_rpcs == 1
    assert t.stats.retrans_bytes == 100 + 64


def test_rpc_drop_charges_nothing():
    t = _raw_transport()
    from repro.core.faults import FaultInjector
    t.install_faults(FaultInjector([Fault("drop", op="rpc", count=1)]))
    with t.act_as("src"):
        with pytest.raises(RpcTimeout):
            t.rpc("dst", "ping", b"x" * 100)
    assert t.stats.rpcs == 0 and t.stats.bytes_sent == 0


# -- satellite: one modeled-wire formula --------------------------------------

def test_modeled_wire_single_formula_equivalence():
    """The stats method, the module function, and the benchmark helper
    must all agree with the historical inline arithmetic."""
    t = _raw_transport()
    with t.act_as("src"):
        t.rpc("dst", "ping", b"x" * 1000)
    s = t.stats
    legacy = (s.bytes_sent / NET_BW_BPS
              + (s.rpcs + s.one_sided_writes) * NET_LAT_WRITE_S
              + s.one_sided_reads * NET_LAT_READ_S)
    assert s.modeled_wire_s() == pytest.approx(legacy)
    assert modeled_wire_s(bytes_sent=s.bytes_sent, rpcs=s.rpcs
                          ) == pytest.approx(legacy)
    assert modeled_us(bytes_sent=s.bytes_sent, rpcs=s.rpcs
                      ) == pytest.approx(legacy * 1e6)


# -- satellite: epoch invalidations are counted -------------------------------

def test_epoch_invalidation_counter(tmp_path):
    c = make(tmp_path)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/e/x", b"v")
        ls.fsync()
        assert ls.stats["epoch_invalidations"] == 0
        c.cm.bump_epoch()  # watcher pushes the new view to the node
        ls.put("/e/y", b"w")  # next op notices the bump
        assert ls.stats["epoch_invalidations"] == 1
        # published in the node registry dump, not a private dict
        assert c.sharedfs["node0"].metrics.to_dict()["counters"][
            "proc.p.epoch_invalidations"] == 1
        ls.put("/e/z", b"u")  # no further bump: no further count
        assert ls.stats["epoch_invalidations"] == 1
    finally:
        c.close()


# -- tracing: write pipeline --------------------------------------------------

def _span_names(tracer, tid):
    return [s.name for s in tracer.spans(tid)]


def _assert_ordered(spans):
    seqs = [s.seq for s in spans]
    ts = [s.t for s in spans]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(a <= b for a, b in zip(ts, ts[1:]))


def test_put_trace_spans_chain_on_one_trace_id(tmp_path):
    """A single traced put produces ONE trace whose spans cover append,
    both replication hops (distinct nodes), the ack, and the digest
    fan-out — linked by the trace id carried in RPC headers."""
    c = make(tmp_path, replication=3)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/t/x", b"v" * 4096)
        ls.fsync()
        ls.digest()
        tr = c.transport.tracer
        tids = tr.find("op.put")
        assert len(tids) == 1
        spans = tr.spans(tids[0])
        names = [s.name for s in spans]
        assert names[0] == "op.put"
        assert "append" in names and "ack" in names
        hop_nodes = {s.node for s in spans
                     if s.name == "rpc.chain_continue"}
        assert hop_nodes == {"node1", "node2"}  # both hops, one trace
        assert names.index("append") < names.index("ack")
        digest_nodes = {s.node for s in spans if s.name == "digest.apply"}
        assert digest_nodes == {"node0", "node1", "node2"}
        _assert_ordered(spans)
    finally:
        c.close()


def test_group_commit_and_background_digest_join_the_put_trace(tmp_path):
    c = make(tmp_path, replication=3, group_commit=True)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/g/x", b"v" * 4096)
        ls.fsync()           # through the group-commit coordinator
        ls.seal_and_digest()  # background digest worker
        ls.drain()
        c.sharedfs["node0"].drain_digests()
        tr = c.transport.tracer
        tids = tr.find("op.put")
        assert len(tids) == 1
        names = _span_names(tr, tids[0])
        assert "gc.batch" in names    # flusher thread joined the trace
        assert "repl.ack" in names
        assert "seal" in names        # seal handoff carried the ctx
        assert "digest.region" in names  # digest worker joined too
        _assert_ordered(tr.spans(tids[0]))
    finally:
        c.close()


def test_trace_header_rides_rpcs_like_epoch(tmp_path):
    """Explicit `_trace` header: the receiver resolves the id and spans
    recorded inside the handler land in the sender's trace."""
    c = make(tmp_path)
    try:
        tr = c.transport.tracer
        ctx = tr.start("op.test", "node0")
        with c.transport.act_as("node0"):
            c.transport.rpc("node1", "read_remote", "/nope",
                            _trace=ctx.trace_id)
        names = _span_names(tr, ctx.trace_id)
        assert "rpc.read_remote" in names
    finally:
        c.close()


def test_sampling_is_deterministic(tmp_path):
    c = make(tmp_path, trace_sampling=1 / 4)
    try:
        ls = c.open_process("p", "node0")
        for i in range(16):
            ls.put(f"/s/{i}", b"v")
            ls.fsync()  # ack closes the pending trace each round
        tr = c.transport.tracer
        assert len(tr.find("op.put")) == 4  # exactly every 4th
        c.set_trace_sampling(0.0)
        before = len(tr.traces())
        ls.put("/s/off", b"v")
        assert len(tr.traces()) == before  # disabled: no allocation
    finally:
        c.close()


# -- tracing: read pipeline ---------------------------------------------------

def test_remote_read_trace_tier_walk_and_verify(tmp_path):
    c = make(tmp_path)
    try:
        w = c.open_process("w", "node0")
        r = c.open_process("r", "node2")  # off-chain: remote read
        w.put("/r/x", b"v" * 4096)
        w.digest()
        tr = c.transport.tracer
        assert r.get("/r/x") == b"v" * 4096
        tids = [t for t in tr.find("op.get")
                if "verify" in _span_names(tr, t)]
        assert tids, "remote verified read produced no op.get trace"
        spans = tr.spans(tids[-1])
        names = [s.name for s in spans]
        tiers = [s.meta.get("tier") for s in spans if s.name == "tier"]
        assert "remote" in tiers      # walked down to the remote tier
        assert "verify" in names      # one-sided pull was checked
        _assert_ordered(spans)
    finally:
        c.close()


def test_read_repair_joins_the_read_trace(tmp_path):
    c = make(tmp_path)
    try:
        w = c.open_process("w", "node0")
        r = c.open_process("r", "node2")
        val = bytes(range(256)) * 32
        w.put("/rr/x", val)
        w.digest()
        assert c.corrupt_at_rest("node0", "/rr/x", seed=11)
        tr = c.transport.tracer
        assert r.get("/rr/x") == val  # detect -> verified RPC -> repair
        tids = [t for t in tr.find("repair")]
        assert tids, "read-repair recorded no span"
        names = _span_names(tr, tids[-1])
        assert "rpc.read_verified" in names
        assert c.sharedfs["node0"].stats["repairs"] >= 1
    finally:
        c.close()


# -- tracing: fail-over -------------------------------------------------------

def test_failover_trace_promotion_replay_lease_migration(tmp_path):
    c = make(tmp_path, replication=2)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/f/x", b"v" * 1024)
        ls.fsync()
        c.kill_node("node0")
        c.detect_failures_now()
        ls2 = c.failover_process("p")
        for sfs in c.sharedfs.values():
            if sfs.node_id not in c.dead_nodes:
                sfs.drain_digests()
        tr = c.transport.tracer
        tids = tr.find("op.failover")
        assert len(tids) == 1
        spans = tr.spans(tids[0])
        names = [s.name for s in spans]
        assert "failover.target" in names
        assert "failover.promote" in names
        assert "failover.lease_migrate" in names
        assert "failover.replay" in names  # background replay joined
        assert names.index("failover.promote") \
            < names.index("failover.lease_migrate")
        _assert_ordered(spans)
        assert ls2.get("/f/x") == b"v" * 1024
    finally:
        c.close()


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_ring_is_bounded():
    rec = FlightRecorder("n", capacity=4)
    for i in range(10):
        rec.record("e", str(i))
    evs = rec.events()
    assert len(evs) == 4
    assert [e[3] for e in evs] == ["6", "7", "8", "9"]  # oldest dropped
    assert [e[0] for e in evs] == sorted(e[0] for e in evs)


def test_flight_recorder_survives_kill_node_with_crash_point(tmp_path):
    """The black box: a node killed by an injected crash point is
    readable post-mortem, and the last events include the crash point
    that killed it."""
    c = make(tmp_path)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/k/a", b"acked")
        ls.fsync()
        c.inject_faults([Fault("crash", op="chain.mid", dst="node0")])
        ls.put("/k/b", b"doomed")
        with pytest.raises(NodeDown):
            ls.fsync()
        assert "node0" in c.dead_nodes
        # post-mortem: ring of the DEAD node, read through the harness
        crashes = c.flight_recording("node0", "crash")
        assert [e[3] for e in crashes] == ["chain.mid"]
        kinds = [e[2] for e in c.flight_recording("node0")]
        assert "kill" in kinds
        assert kinds.index("crash") < kinds.index("kill")
        # the surviving replica's ring shows the writer's traffic
        assert "rpc" in [e[2] for e in c.flight_recording("node1")]
    finally:
        c.close()


def test_flight_recorder_captures_epoch_and_digest_events(tmp_path):
    c = make(tmp_path)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/fr/x", b"v")
        ls.fsync()
        ls.digest()
        assert c.flight_recording("node0", "digest")
        c.cm.bump_epoch()
        epochs = c.flight_recording("node1", "epoch")
        assert [e[3] for e in epochs] == [str(c.cm.epoch)]
    finally:
        c.close()


def test_flight_recorder_records_injected_faults(tmp_path):
    c = make(tmp_path)
    try:
        ls = c.open_process("p", "node0")
        ls.put("/ff/x", b"v")
        c.inject_faults([Fault("dup", op="rpc", dst="node1", count=1)])
        ls.fsync()
        faults = c.flight_recording("node1", "fault")
        assert faults and faults[0][3].startswith("dup:rpc:")
    finally:
        c.close()


# -- interval spans -----------------------------------------------------------

class _Sink:
    """A span sink that records (event, name, counts) at entry and exit."""

    def __init__(self):
        self.events = []

    def __call__(self, name, counts):
        sink = self

        class _Open:
            def __enter__(self):
                sink.events.append(("enter", name, dict(counts)))

            def __exit__(self, *exc):
                sink.events.append(("exit", name, dict(counts)))

        return _Open()


def test_span_nests_in_entry_order_and_fills_duration():
    t = [0.0]
    tr = Tracer(clock=lambda: t[0], sampling=1.0, sink=None)
    ctx = tr.start("op.test")
    tok = tr.push(ctx)
    try:
        with tr.span("outer", nbytes=8):
            t[0] = 1.0
            with tr.span("inner", trace_as="inner.op", meta={"k": 1}):
                t[0] = 3.0
            t[0] = 4.0
    finally:
        tr.pop(tok)
    spans = tr.spans(ctx.trace_id)
    assert [s.name for s in spans] == ["op.test", "outer", "inner.op"]
    _assert_ordered(spans)
    assert spans[0].dur is None  # a point annotation stays a point
    assert spans[1].dur == 4.0 and spans[2].dur == 2.0
    assert spans[1].meta == {"nbytes": 8} and spans[2].meta == {"k": 1}
    assert spans[2].to_dict()["dur"] == 2.0


def test_span_passes_counts_to_the_sink():
    sink = _Sink()
    tr = Tracer(sink=sink)
    with tr.span("store.replicate", node="node1", nbytes=10) as sp:
        sp.count(entries=3)
    assert sink.events == [
        ("enter", "store.replicate", {"node": "node1", "nbytes": 10}),
        ("exit", "store.replicate",
         {"node": "node1", "nbytes": 10, "entries": 3})]
    assert sp.seconds >= 0 and sp.counts["entries"] == 3


def test_span_without_sink_or_trace_records_nothing():
    tr = Tracer(sampling=1.0, sink=None)
    with tr.span("ckpt.d2h", nbytes=4) as sp:
        pass
    assert tr.traces() == [] and sp.seconds >= 0
    sink = _Sink()
    tr = Tracer(sampling=1.0, sink=sink)
    ctx = tr.start("op.other")  # started, but not current on this thread
    with tr.span("ckpt.d2h"):
        pass
    assert [s.name for s in tr.spans(ctx.trace_id)] == ["op.other"]
    assert [e[0] for e in sink.events] == ["enter", "exit"]


def test_span_closes_when_its_work_raises():
    sink = _Sink()
    tr = Tracer(clock=lambda: 5.0, sampling=1.0, sink=sink)
    ctx = tr.start("op.fail")
    tok = tr.push(ctx)
    try:
        with pytest.raises(ValueError):
            with tr.span("store.persist") as sp:
                raise ValueError("boom")
    finally:
        tr.pop(tok)
    assert [e[0] for e in sink.events] == ["enter", "exit"]
    assert tr.spans(ctx.trace_id)[-1].dur == 0.0
    assert sp.end >= sp.start > 0


def test_default_sink_is_the_jax_profiler_only_where_jax_is_loaded(
        monkeypatch):
    import sys

    from repro.core.obs import profiler_sink
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert profiler_sink("x", {}) is None
    assert Tracer().sink is profiler_sink


def test_cluster_spans_reach_the_sink_set_on_the_cluster(tmp_path):
    c = make(tmp_path, replication=3)
    try:
        sink = _Sink()
        c.set_span_sink(sink)
        ls = c.open_process("p", "node0")
        ls.put("/sp/x", b"v" * 64)
        ls.fsync()
        entered = [(n, k) for e, n, k in sink.events if e == "enter"]
        assert ("store.append", {"node": "node0", "nbytes": 64}) in entered
        assert {n for n, _ in entered} >= {"store.persist",
                                            "store.replicate", "repl.hop"}
        hops = [k["node"] for e, n, k in sink.events
                if e == "enter" and n == "repl.hop"]
        assert hops == ["node1", "node2"]  # one span per hop
        done = {n: k for e, n, k in sink.events if e == "exit"}
        assert done["store.replicate"]["entries"] == 1
        assert done["store.persist"]["nbytes"] > 64
        c.set_span_sink(None)
        n = len(sink.events)
        ls.put("/sp/y", b"v")
        assert len(sink.events) == n
    finally:
        c.close()
