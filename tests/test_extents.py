"""Extent-granularity IO: byte-range writes through every layer.

Covers the ExtentOverlay primitive, the OP_WRITE wire format, the
overlay-aware log hashtable and replica mirror, SegmentStore patch
chains (recovery + compaction materialization), the end-to-end
LibState read assembly, range-aware coalescing, the tombstone-
resurrection regression (ISSUE 2 satellite), and replication byte
savings for range writes and delta checkpoints.
"""
import os

import numpy as np
import pytest

from repro.core import log as L
from repro.core.extents import ExtentOverlay, splice
from repro.core.log import Entry, UpdateLog, decode_stream
from repro.core.replication import ReplicaSlot
from repro.core.segstore import SegmentStore


# -- ExtentOverlay primitive -------------------------------------------------


def test_splice_patches_and_zero_fills():
    assert splice(b"hello", 1, b"XY") == b"hXYlo"
    assert splice(b"ab", 4, b"cd") == b"ab\x00\x00cd"  # hole reads zeros
    assert splice(b"abc", 0, b"") == b"abc"


def test_overlay_latest_wins_and_merges():
    ov = ExtentOverlay()
    ov.write(0, b"aaaa")
    ov.write(2, b"BB")        # overlap: later wins
    ov.write(4, b"cc")        # adjacent: merges into one extent
    assert ov.extents() == [(0, b"aaBBcc")]
    ov.write(10, b"zz")       # disjoint: second extent
    assert len(ov.extents()) == 2
    assert ov.end == 12
    assert ov.apply_to(b"XXXXXXXX") == b"aaBBccXX\x00\x00zz"


def test_overlay_bridges_gap_between_extents():
    ov = ExtentOverlay()
    ov.write(0, b"aa")
    ov.write(6, b"bb")
    ov.write(2, b"1234")      # touches both: all three merge
    assert ov.extents() == [(0, b"aa1234bb")]


def test_overlay_read_range():
    ov = ExtentOverlay()
    ov.write(4, b"abcdef")
    assert ov.read_range(5, 3) == b"bcd"
    assert ov.read_range(2, 4) is None  # not fully covered: needs base
    z = ExtentOverlay(from_zero=True)
    z.write(0, b"xy")
    assert z.read_range(5, 2) == b""  # past EOF: empty, like every tier


# -- OP_WRITE wire format and log index --------------------------------------


def test_entry_offset_roundtrip():
    e = Entry(7, L.OP_WRITE, "/a", b"zz", 4096)
    (d,) = decode_stream(e.encode())
    assert d == e and d.offset == 4096
    # corrupting the offset must fail the CRC, not decode misplaced data
    enc = bytearray(e.encode())
    enc[19] ^= 0xFF  # inside the offset field
    assert decode_stream(bytes(enc)) == []


def test_log_index_patches_full_value(tmp_path):
    lg = UpdateLog(str(tmp_path / "l" / "a.log"))
    lg.append(L.OP_PUT, "/x", b"aaaa")
    lg.append(L.OP_WRITE, "/x", b"BB", 1)
    assert lg.index["/x"] == b"aBBa"  # stays a full value


def test_log_index_overlay_when_base_below(tmp_path):
    lg = UpdateLog(str(tmp_path / "l" / "a.log"))
    lg.append(L.OP_WRITE, "/x", b"BB", 2)
    ov = lg.index["/x"]
    assert isinstance(ov, ExtentOverlay) and not ov.from_zero
    assert ov.apply_to(b"aaaaaa") == b"aaBBaa"


def test_log_write_after_delete_is_zero_based(tmp_path):
    lg = UpdateLog(str(tmp_path / "l" / "a.log"))
    lg.append(L.OP_PUT, "/x", b"old!")
    lg.append(L.OP_DELETE, "/x")
    lg.append(L.OP_WRITE, "/x", b"n", 2)
    ov = lg.index["/x"]
    assert ov.from_zero  # the delete cut the base: holes read zero
    assert ov.apply_to(b"") == b"\x00\x00n"


def test_log_recovery_replays_range_writes(tmp_path):
    p = str(tmp_path / "l" / "a.log")
    lg = UpdateLog(p)
    lg.append(L.OP_PUT, "/x", b"aaaa")
    lg.append(L.OP_WRITE, "/x", b"ZZ", 2)
    lg.persist()
    lg.close()
    lg2 = UpdateLog(p)
    assert lg2.index["/x"] == b"aaZZ"
    lg2.close()


# -- range-aware coalescing ---------------------------------------------------


def _replay(entries):
    state = {}
    for e in entries:
        if e.op == L.OP_PUT:
            state[e.path] = e.data
        elif e.op == L.OP_WRITE:
            state[e.path] = splice(state.get(e.path, b""), e.offset, e.data)
        elif e.op == L.OP_DELETE:
            state.pop(e.path, None)
        elif e.op == L.OP_RENAME:
            if e.path in state:
                state[e.data.decode()] = state.pop(e.path)
    return state


def test_coalesce_folds_write_into_pending_put():
    es = [Entry(1, L.OP_PUT, "/a", b"aaaa"),
          Entry(2, L.OP_WRITE, "/a", b"BB", 1)]
    out = UpdateLog.coalesce(es)
    assert [(e.seqno, e.op, e.data) for e in out] == [(2, L.OP_PUT, b"aBBa")]
    assert _replay(out) == _replay(es)


def test_coalesce_merges_overlapping_ranges_keeps_disjoint():
    es = [Entry(1, L.OP_WRITE, "/a", b"aaaa", 0),
          Entry(2, L.OP_WRITE, "/a", b"bb", 2),    # overlaps 1: merge
          Entry(3, L.OP_WRITE, "/a", b"cc", 100)]  # disjoint: kept
    out = UpdateLog.coalesce(es)
    assert len(out) == 2
    assert (out[0].offset, out[0].data) == (0, b"aabb")
    assert (out[1].offset, out[1].data) == (100, b"cc")
    assert _replay(out) == _replay(es)


def test_coalesce_adjacent_appends_collapse():
    es = [Entry(i + 1, L.OP_WRITE, "/a", bytes([65 + i]) * 4, i * 4)
          for i in range(8)]
    out = UpdateLog.coalesce(es)
    assert len(out) == 1 and len(out[0].data) == 32
    assert _replay(out) == _replay(es)


def test_coalesce_delete_kills_ranges():
    es = [Entry(1, L.OP_WRITE, "/a", b"xx", 0),
          Entry(2, L.OP_DELETE, "/a", b"")]
    out = UpdateLog.coalesce(es)
    assert [e.op for e in out] == [L.OP_DELETE]


def test_coalesce_rename_pins_ranges():
    es = [Entry(1, L.OP_WRITE, "/a", b"xx", 0),
          Entry(2, L.OP_RENAME, "/a", b"/b"),
          Entry(3, L.OP_WRITE, "/a", b"yy", 0)]
    out = UpdateLog.coalesce(es)
    assert [e.seqno for e in out] == [1, 2, 3]
    assert _replay(out) == _replay(es)


# -- SegmentStore patch chains ------------------------------------------------


def test_segstore_patch_and_get(tmp_path):
    s = SegmentStore(str(tmp_path / "a"))
    s.put("/x", b"a" * 64)
    s.patch("/x", 8, b"BBBB")
    assert s.get("/x") == b"a" * 8 + b"BBBB" + b"a" * 52
    s.patch("/x", 62, b"zzzz")  # extends past the end
    v = s.get("/x")
    assert len(v) == 66 and v[62:] == b"zzzz"
    assert s.sizes["/x"] == 66 and s.bytes == 66
    s.close()


def test_segstore_patch_missing_path_zero_base(tmp_path):
    s = SegmentStore(str(tmp_path / "a"))
    s.patch("/new", 4, b"hi")
    assert s.get("/new") == b"\x00\x00\x00\x00hi"
    s.close()


def test_segstore_get_range_single_pread(tmp_path):
    s = SegmentStore(str(tmp_path / "a"))
    s.put("/x", bytes(range(100)))
    assert s.get_range("/x", 10, 5) == bytes(range(10, 15))
    assert s.get_range("/x", 98, 10) == bytes([98, 99])  # clamped
    s.patch("/x", 20, b"\xff" * 10)
    assert s.get_range("/x", 22, 4) == b"\xff" * 4   # served by the patch
    assert s.get_range("/x", 15, 10) == bytes(range(15, 20)) + b"\xff" * 5
    s.close()


def test_segstore_patch_survives_recovery(tmp_path):
    root = str(tmp_path / "a")
    s = SegmentStore(root)
    s.put("/x", b"a" * 32)
    s.patch("/x", 4, b"YY")
    s.commit()
    s.close()
    s2 = SegmentStore(root)  # replays base + delta needles
    assert s2.get("/x") == b"a" * 4 + b"YY" + b"a" * 26
    s2.close()


def test_segstore_compaction_materializes_chains(tmp_path):
    s = SegmentStore(str(tmp_path / "a"))
    s.put("/x", b"a" * 1024)
    for i in range(10):
        s.patch("/x", i * 8, b"B" * 8)
    want = s.get("/x")
    s.compact()
    from repro.core.segstore import _PatchChain
    assert not isinstance(s.index["/x"], _PatchChain)  # single needle now
    assert s.get("/x") == want
    s.close()


def test_segstore_long_chain_materializes(tmp_path):
    s = SegmentStore(str(tmp_path / "a"), max_patch_chain=4)
    s.put("/x", b"a" * 64)
    for i in range(8):
        s.patch("/x", i, bytes([48 + i]))
    from repro.core.segstore import _PatchChain
    loc = s.index["/x"]
    chain_len = len(loc.patches) if isinstance(loc, _PatchChain) else 0
    assert chain_len <= 4  # bounded read fan-in
    assert s.get("/x") == b"01234567" + b"a" * 56
    s.close()


# -- ReplicaSlot mirror -------------------------------------------------------


def test_replica_slot_range_write_overlay(tmp_path):
    slot = ReplicaSlot(str(tmp_path / "s" / "p.log"))
    slot.write(None, Entry(1, L.OP_WRITE, "/a", b"BB", 2).encode())
    ov = slot.mirror["/a"]
    assert isinstance(ov, ExtentOverlay) and not ov.from_zero
    slot.write(None, Entry(2, L.OP_PUT, "/b", b"full").encode())
    slot.write(None, Entry(3, L.OP_WRITE, "/b", b"X", 0).encode())
    assert slot.mirror["/b"] == b"Xull"  # full value patched in place
    slot.write(None, Entry(4, L.OP_DELETE, "/a", b"").encode())
    slot.write(None, Entry(5, L.OP_WRITE, "/a", b"z", 1).encode())
    assert slot.mirror["/a"].from_zero  # tombstone-aware overlay
    slot.close()


# -- end-to-end through LibState ---------------------------------------------


def test_range_write_read_your_writes(tmp_cluster):
    ls = tmp_cluster.open_process("p1")
    ls.put("/e/x", b"a" * 1024)
    ls.digest()                      # base now lives in the hot area
    ls.write("/e/x", b"MID", 512)
    v = ls.get("/e/x")               # overlay assembled over L2 base
    assert v[512:515] == b"MID" and v[:512] == b"a" * 512
    ls.digest()                      # patch-in-place digested
    assert ls.get("/e/x")[512:515] == b"MID"
    assert ls.sfs.hot.get("/e/x")[512:515] == b"MID"


def test_range_write_visible_cross_node_after_digest(tmp_cluster):
    w = tmp_cluster.open_process("w", "node0")
    w.put("/cn/x", b"b" * 256)
    w.digest()
    w.write("/cn/x", b"QQ", 100)
    w.digest()
    r = tmp_cluster.open_process("r", "node1")
    v = r.get("/cn/x")
    assert v[100:102] == b"QQ" and len(v) == 256


def test_get_range_exact(tmp_cluster):
    ls = tmp_cluster.open_process("p1")
    ls.put("/gr/x", bytes(range(256)))
    assert ls.get_range("/gr/x", 10, 4) == bytes(range(10, 14))
    ls.digest()
    ls.dram.clear()
    assert ls.get_range("/gr/x", 200, 8) == bytes(range(200, 208))
    ls.write("/gr/x", b"\x01\x02", 50)
    assert ls.get_range("/gr/x", 50, 2) == b"\x01\x02"  # from the overlay
    assert ls.get_range("/missing", 0, 4) is None


def test_write_after_delete_reads_zero_based(tmp_cluster):
    ls = tmp_cluster.open_process("p1")
    ls.put("/wd/x", b"Z" * 64)
    ls.digest()
    ls.delete("/wd/x")
    ls.write("/wd/x", b"new", 4)
    assert ls.get("/wd/x") == b"\x00\x00\x00\x00new"  # no old bytes leak
    ls.digest()
    assert ls.get("/wd/x") == b"\x00\x00\x00\x00new"


def test_rename_of_partially_written_value(tmp_cluster):
    ls = tmp_cluster.open_process("p1")
    ls.put("/rn/src", b"c" * 32)
    ls.digest()                      # base below the log
    ls.write("/rn/src", b"XX", 8)    # overlay in the log
    ls.rename("/rn/src", "/rn/dst")  # must carry base + overlay
    assert ls.get("/rn/src") is None
    v = ls.get("/rn/dst")
    assert v[8:10] == b"XX" and len(v) == 32
    ls.digest()
    assert ls.get("/rn/dst")[8:10] == b"XX"


def test_rename_after_digest_read_your_writes(tmp_cluster):
    """A rename whose source lives only below the log must still be
    readable at the destination before the next digest."""
    ls = tmp_cluster.open_process("p1")
    ls.put("/rd/a", b"moved")
    ls.digest()
    ls.rename("/rd/a", "/rd/b")
    assert ls.get("/rd/b") == b"moved"
    assert ls.get("/rd/a") is None


def test_range_write_replicates_only_the_range(tmp_cluster):
    """Acceptance: >=10x fewer replicated bytes for small range writes
    into a large object vs whole-blob PUT."""
    ls = tmp_cluster.open_process("p1")
    obj = b"\x00" * (1 << 20)
    ls.put("/rr/blob", obj)
    ls.put("/rr/ext", obj)
    ls.fsync()
    tr = ls.transport.stats
    b0 = tr.bytes_sent
    ls.put("/rr/blob", obj[:-3] + b"end")  # whole-value rewrite
    ls.fsync()
    blob_bytes = tr.bytes_sent - b0
    b0 = tr.bytes_sent
    ls.write("/rr/ext", b"end", (1 << 20) - 3)  # 3-byte range write
    ls.fsync()
    ext_bytes = tr.bytes_sent - b0
    assert ext_bytes * 10 <= blob_bytes
    assert ls.get("/rr/ext") == ls.get("/rr/blob")


def test_digest_write_fetches_missing_base_from_peer(tmp_cluster):
    """Digesting a range write on a node whose local base copy is gone
    (epoch invalidation) must fetch the base from a replica peer, not
    patch a fabricated zeros base into the hot area."""
    ls = tmp_cluster.open_process("p1", "node0")
    ls.put("/fb/x", b"A" * 100)
    ls.digest()
    # simulate the epoch-rejoin invalidation dropping node0's copy;
    # node1 (chain replica) still holds the digested base
    ls.sfs.hot.delete("/fb/x")
    ls.sfs.hot.commit()
    ls.dram.clear()
    ls.write("/fb/x", b"B" * 10, 50)
    want = b"A" * 50 + b"B" * 10 + b"A" * 40
    assert ls.get("/fb/x") == want  # overlay over the remote base
    ls.digest()
    ls.dram.clear()
    assert ls.get("/fb/x") == want  # digest must not zero the prefix
    assert ls.sfs.hot.get("/fb/x") == want


def test_read_any_overlay_fetches_missing_base_from_peer(tmp_cluster):
    """Assembling a slot overlay on a node whose base copy is gone must
    fetch the base from a peer (local mode) or report a miss (remote-
    serving mode) — never hand back a fabricated zeros-base value."""
    w = tmp_cluster.open_process("w", "node0")
    w.put("/rb/x", b"C" * 64)
    w.digest()                       # base digested on node0 and node1
    sfs1 = tmp_cluster.sharedfs["node1"]
    sfs1.hot.delete("/rb/x")         # node1 lost its copy (epoch drop)
    sfs1.hot.commit()
    w.write("/rb/x", b"ZZ", 0)
    w.fsync()                        # overlay lands in node1's slot
    found, v = sfs1.read_any("/rb/x")
    assert found and v == b"ZZ" + b"C" * 62  # peer base, not zeros
    found, v = sfs1.read_remote("/rb/x")     # remote-serving mode
    assert (found, v) == (False, None)       # miss: caller keeps walking


def test_recovery_after_coalesced_dsync_keeps_replicas_fresh(tmp_path):
    """recover_process ships the raw log suffix to slots that may hold
    a coalesced stream; entries older than the slot's tail were
    coalesced out and must NOT be appended (they would replay stale
    data over newer and unsort the slot's seqno index)."""
    from repro.core import AssiseCluster
    c = AssiseCluster(str(tmp_path / "c"), n_nodes=2, replication=2,
                      mode="optimistic")
    ls = c.open_process("p", "node0")
    ls.put("/a", b"v1")
    ls.put("/a", b"v2")
    ls.dsync()               # coalesced: ships only the v2 entry
    ls.put("/b", b"x")       # never replicated before the crash
    ls.log.persist()
    c.kill_process(ls)
    ls2 = c.recover_process_local("p", "node0")
    assert ls2.get("/a") == b"v2"
    assert c.sharedfs["node1"].hot.get("/a") == b"v2"  # not stale v1
    assert c.sharedfs["node1"].hot.get("/b") == b"x"
    c.close()


# -- tombstone resurrection regression (satellite) ----------------------------


def test_tombstone_in_slot_is_authoritative(tmp_cluster):
    """delete -> (replicated tombstone) -> get on the replica node must
    miss instead of resurrecting the stale value from another tier."""
    w = tmp_cluster.open_process("w", "node0")
    w.put("/tomb/x", b"old")
    w.digest()                       # value in every chain node's hot area
    w.delete("/tomb/x")
    w.fsync()                        # tombstone only in node1's slot
    # the writer dies without digesting; its leases lapse
    sfs0 = tmp_cluster.sharedfs["node0"]
    sfs0.local_procs.pop("w", None)
    sfs0.lease_mgr.release_all("w")
    sfs1 = tmp_cluster.sharedfs["node1"]
    found, v = sfs1.read_any("/tomb/x")
    assert found and v is None       # tombstone, not a plain miss
    r = tmp_cluster.open_process("r", "node1")
    # node0's hot area still holds the stale value; the tombstone must
    # stop the read from falling through to it
    assert r.get("/tomb/x") is None


def test_tombstone_after_replica_digest(tmp_cluster):
    w = tmp_cluster.open_process("w", "node0")
    w.put("/tomb/y", b"old")
    w.digest()
    w.delete("/tomb/y")
    w.digest()                       # delete digested everywhere
    r = tmp_cluster.open_process("r", "node1")
    assert r.get("/tomb/y") is None


# -- delta checkpoints as range writes ---------------------------------------


def test_delta_checkpoint_replicates_changed_blocks_only(tmp_cluster):
    from repro.ckpt import AssiseCheckpointer, CheckpointConfig
    store = tmp_cluster.open_process("ck")
    ck = AssiseCheckpointer(store, CheckpointConfig(
        prefix="/dck", delta=True, delta_block=256, mode="pessimistic"))
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((1024, 64)).astype(np.float32)  # 256KB
    ck.save(0, {"emb": emb})
    tr = store.transport.stats
    b0 = tr.bytes_sent
    emb2 = emb.copy()
    emb2[3] += 1.0                   # one sparse row update
    ck.save(1, {"emb": emb2})
    repl = tr.bytes_sent - b0
    assert repl < emb.nbytes // 10   # only changed-block bytes shipped
    flat, man = ck.restore()
    assert man["step"] == 1
    np.testing.assert_array_equal(flat["/emb"], emb2)


def test_overlay_base_empty_hot_value_not_stale_cold(tmp_cluster):
    """An empty-bytes hot value is a real base: assembling a slot
    overlay must not fall through to a stale cold copy."""
    sfs = tmp_cluster.sharedfs["node1"]
    sfs.cold.put("/ov/x", b"STALEDATA")
    sfs.cold.commit()
    sfs.hot.put("/ov/x", b"")  # current value: empty
    sfs.hot.commit()
    slot = sfs.slot_for("pz")
    slot.write(None, Entry(1, L.OP_WRITE, "/ov/x", b"AB", 0).encode())
    found, v = sfs.read_any("/ov/x")
    assert found and v == b"AB"


def test_restore_detects_partial_range_save(tmp_cluster):
    """A crash mid-save in range mode leaves partial patches of a newer
    step on the stable keys; restore must return None, never silently
    corrupt tensors (per-leaf manifest CRCs)."""
    from repro.ckpt import AssiseCheckpointer, CheckpointConfig
    store = tmp_cluster.open_process("ckc")
    ck = AssiseCheckpointer(store, CheckpointConfig(
        prefix="/crash", delta=True, delta_block=64))
    ck.save(0, {"w": np.zeros(256, np.float32)})
    assert ck.restore() is not None
    # simulate a crash partway through save(1): one range patch landed,
    # the step-1 manifest never did
    store.write("/crash/data/w", b"\xff" * 16, 200)
    ck2 = AssiseCheckpointer(store, CheckpointConfig(
        prefix="/crash", delta=True, delta_block=64))
    assert ck2.restore() is None


def test_segstore_get_range_base_fast_path(tmp_path):
    """A range wholly inside the base needle with no overlapping patch
    must not assemble the chain."""
    s = SegmentStore(str(tmp_path / "a"))
    s.put("/x", bytes(range(200)))
    s.patch("/x", 150, b"\xee" * 10)
    assert s.get_range("/x", 10, 20) == bytes(range(10, 30))
    assert s.get_range("/x", 145, 10) == bytes(range(145, 150)) + b"\xee" * 5
    s.patch("/x", 300, b"zz")  # extends: hole between 200 and 300
    assert s.get_range("/x", 210, 8) == b"\x00" * 8
    assert s.get_range("/x", 298, 10) == b"\x00\x00zz"
    s.close()


def test_delta_kernel_path_matches_host_scan(tmp_cluster):
    """The checkpointer's device path (the Pallas delta_mask kernel, in
    interpret mode on CPU) must produce the same changed-block set as
    the host scan; off a TPU the checkpointer picks the host scan."""
    from functools import partial

    from repro.ckpt import AssiseCheckpointer
    from repro.ckpt import checkpoint as C
    rng = np.random.default_rng(1)
    old = rng.integers(0, 256, 64 * 512 + 100, dtype=np.uint8).tobytes()
    new = bytearray(old)
    new[70] ^= 0xFF        # inside the tile-aligned prefix
    new[64 * 512 + 50] ^= 0xFF  # inside the host-scanned tail
    host = C.changed_block_idxs(bytes(new), old, 512)
    kern = C.changed_block_idxs(bytes(new), old, 512, partial(
        C.kernel_changed_blocks, interpret=True))
    assert host == ([0, 64], 0)
    assert kern == ([0, 64], 64 * 512)
    assert AssiseCheckpointer(tmp_cluster.open_process("kp"))._scan is None


def test_changed_extents_merges_runs():
    from repro.ckpt.delta import changed_extents
    new = bytearray(b"a" * 100)
    old = bytes(new)
    new[10] = 66   # block 1 (size 10)
    new[20] = 66   # block 2 — consecutive: one run
    new[95] = 66   # block 9 — separate run, clamped to len
    ext = changed_extents(bytes(new), old, 10)
    assert ext == [(10, 20), (90, 10)]
