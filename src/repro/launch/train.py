"""End-to-end training driver.

Runs an architecture on the default device, with the Assise layer
underneath: every --ckpt-every steps the train state is logged,
chain-replicated to a simulated cache-replica node, and the
data-pipeline cursor is logged with it. --inject-failure kills the worker
process + primary node mid-run and restores from the replica, verifying
that the restored leaves are bit-identical to the last save (the paper's
failover, as a training concern).

`-reduced` configs are the tiny CPU smoke sizes; any other config runs
at its own widths with RunConfig's chunk defaults.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b-reduced \
      --steps 30 --ckpt-every 10
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b-reduced \
      --steps 20 --inject-failure 12 --mode optimistic
"""
from __future__ import annotations

import argparse
import os
import shutil
import tempfile
import time
import zlib
from collections import Counter
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.ckpt import AssiseCheckpointer, CheckpointConfig
from repro.ckpt.checkpoint import _encode_leaf, unflatten_into
from repro.configs import get_config
from repro.core import AssiseCluster
from repro.data import TokenPipeline
from repro.models.transformer import RunConfig, init_params, loss_fn
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

REPO_ROOT = Path(__file__).resolve().parents[3]
# chunk sizes for the tiny `-reduced` configs on the CPU
_SMOKE_CHUNKS = dict(chunk_q=32, chunk_kv=32, mamba_chunk=16, rwkv_chunk=16,
                     loss_chunk=64)


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says (JAX reads it itself), or else at one
    fixed path inside the checkout: the path is part of what the cache
    matches on, so it must not move between runs. Returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_train_step(cfg, rc, opt_cfg):
    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, rc, p, batch), has_aux=True)(params)
        params, opt_state, gnorm = adamw_update(opt_cfg, grads, opt_state,
                                                params)
        return params, opt_state, {"loss": loss, "gnorm": gnorm, **metrics}
    return jax.jit(step_fn, donate_argnums=(0, 1))


def check_restore(flat: Dict, manifest: dict, saved: Dict[str, bytes]
                  ) -> dict:
    """Restored leaves against what the killed process last saved: the
    CRC of every leaf's encoding against the manifest, and every byte of
    the largest leaf. Raises on any mismatch."""
    if set(flat) != set(saved):
        raise RuntimeError(f"restored leaves {sorted(set(flat) ^ set(saved))}"
                           " differ from the saved set")
    bad = [n for n, a in flat.items() if zlib.crc32(_encode_leaf(a))
           != manifest["leaf_crc"][n]]
    if bad:
        raise RuntimeError(f"restored leaves fail their CRC: {bad[:5]}")
    big = max(saved, key=lambda n: len(saved[n]))
    if _encode_leaf(flat[big]) != saved[big]:
        raise RuntimeError(f"restored {big} differs from the saved bytes")
    return {"leaves": len(flat), "crc_ok": len(flat), "compared": big,
            "compared_bytes": len(saved[big])}


@dataclass
class TrainResult:
    losses: List[float]  # every executed step in order, replays included
    compile_s: float  # AOT compile of the train step
    hbm_plan_bytes: int  # the compiled step's arguments + outputs + temps
    phase_s: Dict[str, float]  # seconds per phase, summed over the run
    ckpt_stats: Dict[str, float]  # AssiseCheckpointer.stats, summed
    resumed_at: Optional[int] = None  # losses index of the first replay
    restored_step: Optional[int] = None
    restore_check: Optional[dict] = None
    state: Optional[dict] = None  # final {"params", "opt"}, on the device


def main(argv=None) -> TrainResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b-reduced")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--mode", default="pessimistic",
                    choices=["pessimistic", "optimistic"])
    ap.add_argument("--inject-failure", type=int, default=0,
                    help="kill worker+node after this step, then restore")
    ap.add_argument("--workdir", default=None,
                    help="cluster directory (default: a temporary one, "
                         "removed at exit)")
    ap.add_argument("--no-delta", action="store_true")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    chunks = _SMOKE_CHUNKS if cfg.name.endswith("-reduced") else {}
    rc = RunConfig(param_dtype=jnp.float32, cache_dtype=jnp.float32,
                   **chunks)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=5)
    ckpt_cfg = CheckpointConfig(mode=args.mode, delta=not args.no_delta)

    workdir = args.workdir or tempfile.mkdtemp(prefix="repro_train_")
    # Assise substrate: this worker + one cache replica + one reserve.
    # The hot area is NVM-sized (the paper's nodes hold hundreds of GB):
    # the 1 GiB harness default would spill every save of a full-width
    # state to the cold tier, writing it to disk a second time.
    cluster = AssiseCluster(workdir, n_nodes=3, replication=2,
                            n_reserve=1, mode=args.mode,
                            hot_capacity=64 << 30)
    store = cluster.open_process("trainer0")
    ckpt = AssiseCheckpointer(store, ckpt_cfg)

    pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         global_batch=args.batch, seed=7,
                         frontend=cfg.n_frontend, d_model=cfg.d_model)
    params = init_params(cfg, jax.random.key(0), rc)
    opt_state = adamw_init(params)
    t = time.perf_counter()
    step_fn = make_train_step(cfg, rc, opt_cfg).lower(
        params, opt_state, pipe.batch_at(0)).compile()
    mem = step_fn.memory_analysis()
    res = TrainResult(losses=[], compile_s=time.perf_counter() - t,
                      hbm_plan_bytes=mem.argument_size_in_bytes
                      + mem.output_size_in_bytes - mem.alias_size_in_bytes
                      + mem.temp_size_in_bytes,
                      phase_s=Counter(), ckpt_stats=Counter())
    print(f"compiled train step in {res.compile_s:.2f}s", flush=True)

    t0 = time.time()
    step = 0
    while step < args.steps:
        t = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        res.phase_s["step"] += time.perf_counter() - t
        res.losses.append(loss)
        print(f"step {step:4d} loss {loss:.4f} gnorm "
              f"{float(metrics['gnorm']):.3f}", flush=True)
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            t = time.perf_counter()
            ckpt.save(step, {"params": params, "opt": opt_state},
                      extra={"pipe": pipe.snapshot().decode()})
            print(f"  ckpt@{step} in {time.perf_counter() - t:.2f}s: logged "
                  f"{ckpt.stats['bytes_logged']/1e6:.2f}MB "
                  f"(full would be {ckpt.stats['bytes_full']/1e6:.2f}MB)",
                  flush=True)
        step += 1

        if args.inject_failure and step == args.inject_failure:
            print(">>> injecting failure: killing worker + primary node",
                  flush=True)
            saved = ckpt.last_saved
            res.ckpt_stats.update(ckpt.stats)
            cluster.kill_process(store)
            cluster.kill_node(store.sfs.node_id)
            cluster.detect_failures_now()
            t_f = time.perf_counter()
            store = cluster.failover_process("trainer0")
            ckpt = AssiseCheckpointer(store, ckpt_cfg)
            t = time.perf_counter()
            res.phase_s["failover"] += t - t_f
            restored = ckpt.restore()
            if restored is None:
                raise RuntimeError("no checkpoint on the replica")
            flat, man = restored
            res.phase_s["restore"] += time.perf_counter() - t
            res.restore_check = check_restore(flat, man, saved)
            del saved
            t = time.perf_counter()
            tree = unflatten_into({"params": params, "opt": opt_state}, flat)
            del flat
            params, opt_state = jax.block_until_ready(jax.tree.map(
                jnp.asarray, (tree["params"], tree["opt"])))
            res.phase_s["h2d"] += time.perf_counter() - t
            pipe.restore(man["extra"]["pipe"].encode())
            step = man["step"] + 1
            res.restored_step = man["step"]
            res.resumed_at = len(res.losses)
            print(f">>> failover complete in {time.perf_counter()-t_f:.3f}s"
                  f"; resumed at step {step} (replica node "
                  f"{store.sfs.node_id})", flush=True)
            args.inject_failure = 0  # only once

    dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s; "
          f"final loss {res.losses[-1]:.4f} (start {res.losses[0]:.4f})")
    print(f"assise: {store.stats}; transport: "
          f"{cluster.transport.stats.rpcs} rpcs, "
          f"{cluster.transport.stats.bytes_sent/1e6:.1f}MB replicated")
    res.ckpt_stats.update(ckpt.stats)
    res.state = {"params": params, "opt": opt_state}
    for k in ("d2h", "encode", "scan", "put", "fsync"):
        res.phase_s[k] = res.ckpt_stats[f"{k}_s"]
    pipe.close()
    cluster.close()
    if args.workdir is None:
        shutil.rmtree(workdir, ignore_errors=True)
    return res


if __name__ == "__main__":
    main()
