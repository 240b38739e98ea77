#!/usr/bin/env python3
"""Chip smoke: the training-checkpoint path on one TPU chip, end to end.

rwkv6-1.6b at its published widths, cut to one chip's share (2 of its
24 layers and a quarter of its vocabulary; why, in
src/repro/configs/rwkv6_1_6b_2l_v16k.py), trains through the normal entry
point, ``repro.launch.train.main``, twice in this one process:

  A  STEPS steps, no saves;
  B  the same seed, saving every EVERY steps through AssiseCheckpointer
     in delta mode (from the second save on, the compiled ``delta_mask``
     kernel finds the changed blocks); after step FAIL_AT - 1 the worker
     and its node are killed, the process fails over to the replica,
     restores the last save and resumes to step STEPS.

Each check is fatal: the restored leaves are bit-identical to the last
save (per-leaf CRC and a byte compare of the largest leaf, inside
``train.main``); B's losses and final embedding equal A's bit for bit;
on the full-size embedding leaf, the compiled kernel's changed-block set
equals the host scan's. Earlier lines give the device, the step compile
and the persistent cache's hits, seconds per phase, bytes through the
kernel and the host scan, peak HBM and the bytes the simulated cluster
wrote. The last line is one JSON object. There is no CPU path: without
a TPU it exits non-zero.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import resource
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "rwkv6-1.6b-2l-v16k"
BATCH, SEQ = 12, 2048  # 13.3 GB of the chip's 15.75 GB HBM (compile plan)
STEPS, EVERY, FAIL_AT = 8, 3, 7  # saves after steps 2 and 5; resume at 6
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}


class SmokeFailure(RuntimeError):
    pass


def _dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(path) for f in fs)


def kernel_vs_host(old: bytes, news: dict, block: int, *,
                   interpret: bool) -> dict:
    """Changed blocks of each encoding in ``news`` against ``old``: the
    checkpointer's kernel scan of the tile-aligned prefix plus the host
    tail, against the host scan of the whole encoding. Returns
    {case: blocks changed}; raises SmokeFailure on a mismatch."""
    from functools import partial

    from repro.ckpt.checkpoint import (changed_block_idxs,
                                       kernel_changed_blocks)
    from repro.ckpt.delta import changed_blocks
    scan = partial(kernel_changed_blocks, interpret=interpret)
    out = {}
    for case, new in news.items():
        kern, aligned = changed_block_idxs(new, old, block, scan)
        host = changed_blocks(new, old, block)
        if not aligned or kern != host:
            raise SmokeFailure(f"{case}: kernel mask ({len(kern)} blocks, "
                               f"{aligned} bytes scanned) != host scan "
                               f"({len(host)} blocks)")
        out[case] = len(host)
    return out


def run(arch: str = ARCH, batch: int = BATCH, seq: int = SEQ, *,
        interpret: bool = False) -> dict:
    """Runs A and B and every check; returns the report. ``interpret``
    runs the mask check's kernel in interpret mode (CPU rehearsal)."""
    import jax
    import numpy as np

    from repro.ckpt.checkpoint import _encode_leaf
    from repro.launch import train

    cache = {"hits": 0, "misses": 0}

    def on_event(name, **_):
        if name in CACHE_EVENTS:
            cache[CACHE_EVENTS[name]] += 1

    def train_run(extra):
        before = dict(cache)
        t = time.perf_counter()
        res = train.main(["--arch", arch, "--batch", str(batch),
                          "--seq", str(seq), "--steps", str(STEPS)] + extra)
        stats = jax.devices()[0].memory_stats() or {}
        return res, {"wall_s": time.perf_counter() - t,
                     "compile_s": res.compile_s,
                     "hbm_plan_bytes": res.hbm_plan_bytes,
                     "cache": {k: cache[k] - before[k] for k in cache},
                     "phase_s": dict(res.phase_s),
                     "peak_hbm_bytes": stats.get("peak_bytes_in_use")}

    jax.monitoring.register_event_listener(on_event)
    rep = {"arch": arch, "batch": batch, "seq": seq, "steps": STEPS}
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as wd:
            a, rep["a"] = train_run(["--ckpt-every", "0",
                                     "--workdir", f"{wd}/a"])
            a_losses = a.losses
            a_embed = _encode_leaf(np.asarray(a.state["params"]["embed"]))
            a_m = _encode_leaf(np.asarray(a.state["opt"]["m"]["embed"]))
            del a  # frees A's device state before B
            b, rep["b"] = train_run(["--ckpt-every", str(EVERY),
                                     "--inject-failure", str(FAIL_AT),
                                     "--workdir", f"{wd}/b"])
            rep["b"]["workdir_bytes"] = _dir_bytes(f"{wd}/b")
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    st = b.ckpt_stats
    rep["b"].update(saves=st["saves"], kernel_bytes=st["kernel_bytes"],
                    host_scan_bytes=st["host_scan_bytes"],
                    bytes_full=st["bytes_full"],
                    bytes_logged=st["bytes_logged"],
                    restore_check=b.restore_check,
                    restored_step=b.restored_step)
    b_embed = _encode_leaf(np.asarray(b.state["params"]["embed"]))
    b_losses, resumed_at, restored = b.losses, b.resumed_at, b.restored_step
    del b

    if rep["b"]["saves"] < 2 or rep["b"]["kernel_bytes"] <= 0:
        raise SmokeFailure(f"run B: {rep['b']['saves']} saves, "
                           f"{rep['b']['kernel_bytes']} kernel bytes")
    if resumed_at is None:
        raise SmokeFailure("run B never restored")
    if not all(np.isfinite(a_losses)):
        raise SmokeFailure(f"non-finite losses in run A: {a_losses}")
    if b_losses[:resumed_at] != a_losses[:resumed_at]:
        raise SmokeFailure("run B before the kill diverged from run A")
    tail, ref = b_losses[resumed_at:], a_losses[restored + 1:]
    if tail != ref:
        raise SmokeFailure(f"resumed losses {tail} != run A's {ref}")
    if b_embed != a_embed:
        raise SmokeFailure("final embedding of run B differs from run A's")
    rep["losses"] = {"a": a_losses, "b_resumed": tail}

    # the mask check: identical, sparsely changed and wholly changed leaf
    rng = np.random.default_rng(0)
    block = train.CheckpointConfig().delta_block  # as run B saved with
    sparse = bytearray(a_embed)
    for blk in rng.choice(len(sparse) // block, 7, replace=False):
        sparse[blk * block + int(rng.integers(block))] ^= 0x5A
    sparse[-1] ^= 0x01  # in the host-scanned tail
    rep["mask_check"] = kernel_vs_host(
        a_embed, {"identical": b_embed, "sparse": bytes(sparse),
                  "dense": a_m}, block, interpret=interpret)
    if rep["mask_check"]["sparse"] != 8 or rep["mask_check"]["identical"]:
        raise SmokeFailure(f"mask check counts {rep['mask_check']}")
    return rep


def _print_report(rep: dict, dev) -> None:
    a, b = rep["a"], rep["b"]
    gb = 1e9
    print(f"device: {dev.platform} {dev.device_kind}")
    print(f"config: {rep['arch']} batch {rep['batch']} x seq {rep['seq']},"
          f" {rep['steps']} steps")
    for name, r in (("A", a), ("B", b)):
        print(f"run {name}: step compile {r['compile_s']:.2f}s, persistent "
              f"cache hits {r['cache']['hits']} misses "
              f"{r['cache']['misses']}; wall {r['wall_s']:.2f}s; peak HBM "
              f"{r['peak_hbm_bytes']} bytes in use, step plan "
              f"{r['hbm_plan_bytes']} bytes")
    ph = b["phase_s"]
    print("run B seconds per phase: " + ", ".join(
        f"{k} {ph.get(k, 0.0):.3f}" for k in
        ("step", "d2h", "encode", "scan", "put", "fsync", "failover",
         "restore", "h2d")))
    print(f"run B saves {b['saves']}: {b['bytes_full'] / gb:.3f} GB "
          f"encoded, {b['bytes_logged'] / gb:.3f} GB logged; scanned "
          f"{b['kernel_bytes']} bytes by the kernel, "
          f"{b['host_scan_bytes']} by the host tail scan")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(f"cluster workdir: {b['workdir_bytes'] / gb:.3f} GB written; "
          f"host peak RSS {rss / gb:.3f} GB")
    rc = b["restore_check"]
    print(f"restore of step {b['restored_step']}: {rc['crc_ok']}/"
          f"{rc['leaves']} leaf CRCs match the save; {rc['compared']} "
          f"({rc['compared_bytes']} bytes) byte-identical")
    print(f"losses: A {rep['losses']['a']}")
    print(f"losses: B after resume {rep['losses']['b_resumed']} == A's")
    print(f"kernel mask == host scan on the embedding leaf: "
          f"{rep['mask_check']} changed blocks")


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU: JAX found no backend ({e})",
              file=sys.stderr)
        return 2
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devs[0].platform} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    from repro.launch.train import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    try:
        rep = run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _print_report(rep, devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
