"""Rehearsal of chip_smoke.py on the CPU: its phases at the tiny
rwkv6-1.6b-reduced size, with the checkpointer's kernel scan in
interpret mode; and the script's refusal to run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_on_cpu(monkeypatch, capsys):
    import jax

    from repro.ckpt import checkpoint as C
    from repro.launch import train
    smoke = _load_smoke()
    monkeypatch.setattr(C, "_device_scan", lambda: partial(
        C.kernel_changed_blocks, interpret=True))
    # 512-byte blocks: the tiny leaves still have tile-aligned prefixes
    monkeypatch.setattr(train, "CheckpointConfig",
                        partial(C.CheckpointConfig, delta_block=512))
    rep = smoke.run("rwkv6-1.6b-reduced", batch=2, seq=32, interpret=True)
    b = rep["b"]
    assert b["saves"] == 2 and b["restored_step"] == 5
    assert b["kernel_bytes"] > 0 and b["host_scan_bytes"] > 0
    assert b["restore_check"]["crc_ok"] == b["restore_check"]["leaves"]
    assert rep["losses"]["b_resumed"] == rep["losses"]["a"][6:]
    assert rep["mask_check"]["sparse"] == 8
    assert rep["mask_check"]["dense"] > 0
    assert b["workdir_bytes"] > 0
    smoke._print_report(rep, jax.devices()[0])
    assert "kernel mask == host scan" in capsys.readouterr().out


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_smoke_refuses_cpu(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    for cwd in (ROOT, tmp_path):  # the checkout, and the script alone
        r = _run_script(cwd)
        assert r.returncode != 0
        assert "no TPU" in r.stderr
        for line in r.stdout.splitlines():
            assert not line.startswith("{"), line
