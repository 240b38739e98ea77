"""End-to-end behaviour tests for the paper's system: training + the
Assise layer together (checkpoint -> kill -> failover -> bit-exact
resume), plus baseline-store comparisons."""
import numpy as np
import pytest

from repro.fs import DisaggregatedCluster, NoCacheCluster


@pytest.mark.slow
def test_train_failover_bitexact(tmp_path):
    from repro.launch import train as T
    losses = T.main(["--arch", "gemma3-1b-reduced", "--steps", "10",
                     "--batch", "2", "--seq", "32", "--ckpt-every", "3",
                     "--inject-failure", "7",
                     "--workdir", str(tmp_path / "w1")]).losses
    # reference run without failure, same seeds
    ref = T.main(["--arch", "gemma3-1b-reduced", "--steps", "10",
                  "--batch", "2", "--seq", "32", "--ckpt-every", "3",
                  "--workdir", str(tmp_path / "w2")]).losses
    # the post-failover tail replays the exact same loss trajectory
    np.testing.assert_allclose(losses[-3:], ref[-3:], rtol=1e-6)


def test_disagg_baseline_loses_cache_on_crash(tmp_path):
    c = DisaggregatedCluster(str(tmp_path / "d"))
    cl = c.open_client("c1")
    cl.put("/a", b"123")
    cl.fsync()
    rpcs_before = c.transport.stats.rpcs
    assert cl.get("/a") == b"123"
    cl.crash()
    assert cl.get("/a")[:3] == b"123"  # refetched from server
    assert c.transport.stats.rpcs > rpcs_before


def test_disagg_block_amplification(tmp_path):
    """4KB block rounding: small writes cost full blocks on the wire."""
    c = DisaggregatedCluster(str(tmp_path / "d"), n_servers=2)
    cl = c.open_client("c1")
    cl.put("/small", b"x" * 100)
    cl.fsync()
    # 100B write -> >= 4096B per replica on the wire
    assert c.transport.stats.bytes_sent >= 4096 * 2


def test_nocache_every_op_is_remote(tmp_path):
    c = NoCacheCluster(str(tmp_path / "n"))
    cl = c.open_client("c1")
    base = c.transport.stats.rpcs
    cl.put("/a", b"1")
    assert cl.get("/a") == b"1"
    assert cl.get("/a") == b"1"  # no cache: hits the wire every time
    assert c.transport.stats.rpcs - base == 3


def test_assise_vs_disagg_wire_bytes(tmp_path):
    """The paper's core claim, miniaturized: for small-IO fsync workloads
    Assise moves far fewer wire bytes than the disaggregated design."""
    from repro.core import AssiseCluster
    a = AssiseCluster(str(tmp_path / "a"), n_nodes=2, replication=2)
    la = a.open_process("p")
    d = DisaggregatedCluster(str(tmp_path / "d"), n_servers=2)
    ld = d.open_client("p")
    for i in range(50):
        la.put(f"/m/{i}", b"v" * 64)
        la.fsync()
        ld.put(f"/m/{i}", b"v" * 64)
        ld.fsync()
    assise_bytes = a.transport.stats.bytes_sent
    disagg_bytes = d.transport.stats.bytes_sent
    assert assise_bytes * 10 < disagg_bytes  # >10x wire-byte advantage
    a.close()
