"""A replica slot skips a delivery it already holds whole by its headers
alone: the chain's continue RPC repeats the one-sided write before it,
and the repeat is found and dropped before any decode."""
import os

import pytest

from repro.core import AssiseCluster
from repro.core import log as L
from repro.core import replication as R
from repro.core.log import Entry
from repro.core.replication import ReplicaSlot

ENTRIES = [
    Entry(1, L.OP_PUT, "/a", b"alpha" * 40),
    Entry(2, L.OP_PUT, "/b", b"beta" * 30),
    Entry(3, L.OP_WRITE, "/a", b"XY", 7),
    Entry(4, L.OP_DELETE, "/b", b""),
    Entry(5, L.OP_PUT, "/c", b"gamma" * 20),
    Entry(6, L.OP_RENAME, "/c", b"/d"),
]


def _stream(entries):
    return b"".join(e.encode() for e in entries)


def _state(slot):
    """Everything a delivery can change in a slot."""
    slot._f.flush()
    with open(slot.path, "rb") as f:
        disk = f.read()
    return {"entries": list(slot.entries), "buf": bytes(slot._buf),
            "disk": disk, "size": os.path.getsize(slot.path),
            "mirror": dict(slot.mirror), "locs": dict(slot._locs),
            "offsets": list(slot._offsets), "acked": slot.acked_seqno,
            "digested": slot.digested_seqno}


@pytest.fixture
def decodes(monkeypatch):
    """Counts the slot's calls to ``decode_stream``."""
    calls = []

    def counted(buf):
        calls.append(len(buf))
        return L.decode_stream(buf)

    monkeypatch.setattr(R, "decode_stream", counted)
    return calls


def _repeat(slot):
    slot.write(None, _stream(ENTRIES))
    return _stream(ENTRIES)


def _truncated(slot):
    slot.write(None, _stream(ENTRIES[:4]))
    slot.truncate_through(4)  # slot empty: the tail is digested_seqno
    assert not slot.entries and slot.digested_seqno == 4
    return _stream(ENTRIES[1:4])


def _torn_tail(slot):
    slot.write(None, _stream(ENTRIES[:4]))
    return _stream(ENTRIES[2:4]) + ENTRIES[4].encode()[:-3]


@pytest.mark.parametrize("setup,held", [
    (_repeat, len(_stream(ENTRIES))),
    (_truncated, len(_stream(ENTRIES[1:4]))),
    (_torn_tail, len(_stream(ENTRIES[2:4]))),
], ids=["repeat", "at-or-below-digested", "held-prefix-torn-tail"])
def test_a_held_delivery_is_skipped_whole_before_any_decode(
        tmp_path, decodes, setup, held):
    slot = ReplicaSlot(str(tmp_path / "s" / "p.log"))
    data = setup(slot)
    before = _state(slot)
    n_dec = len(decodes)
    assert slot.write(None, data) == held
    assert len(decodes) == n_dec  # headers only: no decode, no CRC
    assert _state(slot) == before
    assert (slot.held_deliveries, slot.held_bytes) == (1, held)
    slot.close()


@pytest.mark.parametrize("cut", range(1, len(ENTRIES)))
def test_an_overlapping_delivery_equals_one_delivery_of_the_stream(
        tmp_path, decodes, cut):
    whole = ReplicaSlot(str(tmp_path / "w" / "p.log"))
    assert whole.write(None, _stream(ENTRIES)) == 0
    parts = ReplicaSlot(str(tmp_path / "o" / "p.log"))
    parts.write(None, _stream(ENTRIES[:cut]))
    n_dec = len(decodes)
    # held entries first, then new ones: decoded, the held ones dropped
    assert parts.write(None, _stream(ENTRIES)) == 0
    assert len(decodes) == n_dec + 1
    assert _state(parts) == _state(whole)
    assert parts.held_deliveries == 0
    for s in (whole, parts):
        s.close()
    reopened = ReplicaSlot(str(tmp_path / "o" / "p.log"))
    assert reopened.entries == ENTRIES
    assert reopened.mirror == whole.mirror
    reopened.close()


def test_each_chain_hop_counts_the_bytes_its_one_sided_write_brought(
        tmp_path):
    exits = []

    def sink(name, counts):
        class _Open:  # the counts as they stand when the span ends
            def __enter__(self):
                pass

            def __exit__(self, *exc):
                exits.append((name, dict(counts)))
        return _Open()

    # the save cells' cluster: node0 writes, node1 and reserve node2 hold
    c = AssiseCluster(str(tmp_path / "c"), n_nodes=3, replication=2,
                      n_reserve=1, mode="pessimistic")
    try:
        c.set_span_sink(sink)
        ls = c.open_process("p", "node0")
        ls.put("/h/x", b"v" * 4096)
        ls.fsync()
        hops = {k["node"]: k for n, k in exits if n == "repl.hop"}
        assert set(hops) == {"node1", "node2"}
        for node, k in hops.items():
            assert k["held_bytes"] == k["nbytes"] > 4096, node
            slot = c.sharedfs[node].slots["p"]
            assert (slot.held_deliveries, slot.held_bytes) == (
                1, k["nbytes"])
            assert slot.mirror["/h/x"] == b"v" * 4096  # ingested once
    finally:
        c.close()
