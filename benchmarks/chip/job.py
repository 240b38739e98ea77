"""The client: a training job over the program under test.

Written step for step as the program's own training loop does it
(``repro.launch.train.main``): build the batch, run the compiled step,
read the loss, save through ``AssiseCheckpointer`` at the traffic's
cadence, and on a kill fail over to the replica, restore and resume.
What it drives is the library underneath: the jitted model step and
AdamW, ``AssiseCheckpointer.save/restore``, the ``delta_mask`` kernel
and the simulated Assise cluster (log, chain replication, digest,
failover).
"""
from __future__ import annotations

import fcntl
import os
import shutil
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, spec, weights

PROC = "trainer0"
# The cluster's root: on /dev/shm, memory-backed like the paper's PMM hot
# tier, so a save's log, replica and digest writes spare the host's disk.
# One fixed name, so a killed run's leftover is found and cleared by the
# next run whatever its checkout; a lock keeps two live runs apart.
STORE_ROOT = "/dev/shm/chipbench-store"
MEMORY_FS = "tmpfs"


def fs_type(path: str) -> str:
    """The type of the filesystem that holds ``path`` (/proc/mounts)."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _, mnt, typ = line.split()[:3]
            inside = path == mnt or path.startswith(mnt.rstrip("/") + "/")
            if inside and len(mnt) > len(best):
                best, kind = mnt, typ
    return kind


class Job:
    """One training job on one chip. ``build``, ``run_first_steps``,
    ``open_store``, ``warm_kernel`` and ``drain`` are its set-up;
    ``step``, ``save`` and ``kill_and_resume`` are what the traffic's
    window calls."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 store_root: str = STORE_ROOT, memory_fs: bool = True,
                 run_config: Optional[dict] = None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.batch, self.seq = traffic["batch"], traffic["seq"]
        self.tokens_per_step = self.batch * self.seq
        self.run_config = run_config or {}
        self.store_root, self.memory_fs = store_root, memory_fs
        self.cluster_root: Optional[str] = None
        self.store_fs: Optional[str] = None
        self._lock = None
        self.cursor = 0  # the next batch's step, saved with each save
        self.loss_log: list = []  # (step, loss) of every step run
        self.cluster = self.store = self.ckpt = None
        self.phase: Dict[str, float] = {}
        self.save_log: list = []  # one record per save
        self.recoveries: list = []
        self.first_steps: Optional[dict] = None

    # -- set-up ---------------------------------------------------------------
    def _batch(self, step: int) -> dict:
        return weights.batch_at(self.seed, step, self.batch, self.seq,
                                self.cfg["vocab_size"])

    def build(self) -> None:
        from repro.launch.train import make_train_step
        from repro.models.transformer import RunConfig, init_params
        from repro.optim.adamw import AdamWConfig, adamw_init
        t = time.perf_counter()
        self.arch = spec.arch(self.cfg["arch"]).arch_config(self.cfg)
        self.rc = RunConfig(param_dtype=jnp.float32,
                            cache_dtype=jnp.float32, **self.run_config)
        self.opt_cfg = AdamWConfig(**self.cfg["optimizer"])
        self.shapes = jax.eval_shape(
            lambda: init_params(self.arch, jax.random.key(0), self.rc))
        self.params = weights.make_params(self.shapes, self.seed)
        self.opt = adamw_init(self.params)
        self.opt_shapes = jax.eval_shape(adamw_init, self.shapes)
        jax.block_until_ready((self.params, self.opt))
        self.phase["init_s"] = time.perf_counter() - t
        t = time.perf_counter()
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in self._batch(0).items()}
        self.step_fn = make_train_step(self.arch, self.rc,
                                       self.opt_cfg).lower(
            self.params, self.opt, batch).compile()
        mem = self.step_fn.memory_analysis()
        self.plan_bytes = (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes
                           + mem.temp_size_in_bytes)
        self.phase["compile_s"] = time.perf_counter() - t

    def run_first_steps(self, n: int = 3) -> None:
        """The job's first ``n`` steps, through the window's own call,
        with what the correctness check reads of them: each loss, the
        first step's gradient as AdamW got it (from its first moment),
        and each leaf's change over the ``n`` steps."""
        t = time.perf_counter()
        b1 = self.cfg["optimizer"]["b1"]
        norms = jax.jit(lambda tree: {k: jnp.sqrt(jnp.sum(jnp.square(x)))
                                      for k, x in _named(tree).items()})
        out = {"losses": []}
        for i in range(n):
            out["losses"].append(self.step())
            if i == 0:
                out["grad_norms"] = {k: float(v) / (1 - b1) for k, v in
                                     norms(self.opt["m"]).items()}
        start = weights.make_params(self.shapes, self.seed)
        diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
        out["update_norms"] = {k: float(v) for k, v in
                               norms(diff(self.params, start)).items()}
        del start
        self.first_steps = out
        self.phase["first_steps_s"] = time.perf_counter() - t

    def open_store(self) -> None:
        """Opens the cluster at ``store_root``, after clearing a leftover
        of an earlier run there. Refuses, rather than moving to another
        medium, where the root is not memory-backed (unless
        ``memory_fs`` is off), is held by another live run, or cannot
        hold the cell."""
        from repro.ckpt import AssiseCheckpointer, CheckpointConfig
        from repro.core import AssiseCluster
        cl = self.cfg["cluster"]
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(self.state()))
        need = cl["root_bytes_per_state_byte"] * state_bytes
        root = self.store_root
        parent = os.path.dirname(root)
        self.store_fs = fs_type(parent)
        if self.memory_fs and self.store_fs != MEMORY_FS:
            raise RuntimeError(f"{parent} is {self.store_fs}, not "
                               f"{MEMORY_FS}: the cluster needs a "
                               "memory-backed root")
        self._lock = open(root + ".lock", "w")
        try:
            fcntl.flock(self._lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except BlockingIOError:
            self._lock.close()
            self._lock = None
            raise RuntimeError(f"another run holds {root}") from None
        if os.path.exists(root):
            shutil.rmtree(root)  # a killed run's leftover
        free = shutil.disk_usage(parent).free
        if free < need:
            raise RuntimeError(f"{parent} has {free} bytes free; the "
                               f"cluster needs {need}")
        self.cluster_root = root
        self.cluster = AssiseCluster(
            self.cluster_root, n_nodes=cl["n_nodes"],
            replication=cl["replication"], n_reserve=cl["n_reserve"],
            mode=cl["mode"],
            hot_capacity=int(cl["hot_capacity_per_state_byte"]
                             * state_bytes))
        self.store = self.cluster.open_process(PROC)
        self.ckpt_cfg = CheckpointConfig(**self.cfg["checkpoint"])
        self.ckpt = AssiseCheckpointer(self.store, self.ckpt_cfg)

    def warm_kernel(self) -> None:
        """Compiles the changed-block scan for each tile-aligned leaf
        size this state saves, so the window's save compiles nothing."""
        from repro.ckpt.checkpoint import _KERNEL_BPT, _encode_leaf
        if self.ckpt is None or self.ckpt._scan is None \
                or not self.ckpt_cfg.delta:
            return
        tile = self.ckpt_cfg.delta_block * _KERNEL_BPT
        sizes = {len(_encode_leaf(np.empty(x.shape, x.dtype))) // tile * tile
                 for x in {(x.shape, x.dtype): x for x in
                           jax.tree.leaves(self.state())}.values()}
        for aligned in sorted(s for s in sizes if s):
            z = bytes(aligned)
            self.ckpt._scan(z, z, self.ckpt_cfg.delta_block)

    def drain(self) -> None:
        """Waits until the store has no background digest left."""
        if self.store is None:
            return
        self.store.drain()
        for nid, sfs in self.cluster.sharedfs.items():
            if nid not in self.cluster.dead_nodes:
                sfs.drain_digests()

    # -- the window's operations ---------------------------------------------
    def state(self) -> dict:
        return {"params": self.params, "opt": self.opt}

    def step(self) -> float:
        with jax.profiler.TraceAnnotation("bench.batch"):
            step = self.cursor
            batch = jax.device_put(self._batch(step))
        with jax.profiler.TraceAnnotation("bench.step"):
            self.params, self.opt, metrics = self.step_fn(
                self.params, self.opt, batch)
            loss = float(metrics["loss"])
        self.cursor += 1
        self.loss_log.append((step, loss))
        return loss

    def save(self) -> None:
        fps = check.device_fingerprints(self.state())
        stats0 = dict(self.ckpt.stats)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.save"):
            self.ckpt.save(self.cursor - 1, self.state(),
                           extra={"cursor": self.cursor})
            self.ckpt.wait()
        stall = time.perf_counter() - t
        self.save_log.append({
            "step": self.cursor - 1, "stall_s": stall, "fingerprints": fps,
            "stats": {k: self.ckpt.stats[k] - stats0[k] for k in stats0}})

    def kill_and_resume(self) -> dict:
        """Kills the worker and its node, drops its device state, fails
        over to the replica, restores the last save, uploads it and runs
        the first resumed step. Returns the recovery's record."""
        from repro.ckpt import AssiseCheckpointer
        from repro.ckpt.checkpoint import unflatten_into
        saved = self.save_log[-1]
        rec = {"saved_step": saved["step"], "log_at_kill": len(self.loss_log)}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.kill"):
            node = self.store.sfs.node_id
            self.cluster.kill_process(self.store)
            self.cluster.kill_node(node)
            for x in jax.tree.leaves(self.state()):
                x.delete()
            self.params = self.opt = None
        with jax.profiler.TraceAnnotation("bench.failover"):
            t = time.perf_counter()
            self.cluster.detect_failures_now()
            self.store = self.cluster.failover_process(PROC)
            self.ckpt = AssiseCheckpointer(self.store, self.ckpt_cfg)
            rec["failover_s"] = time.perf_counter() - t
        with jax.profiler.TraceAnnotation("bench.restore"):
            t = time.perf_counter()
            restored = self.ckpt.restore()
            if restored is None:
                raise RuntimeError("no checkpoint on the replica")
            flat, man = restored
            rec["restored"] = flat  # compared after the window
            rec["restored_step"] = man["step"]
            tree = unflatten_into({"params": self.shapes,
                                   "opt": self.opt_shapes}, flat)
            rec["restore_read_s"] = time.perf_counter() - t
        with jax.profiler.TraceAnnotation("bench.h2d"):
            state = jax.block_until_ready(jax.device_put(tree))
            del tree
            self.params, self.opt = state["params"], state["opt"]
            self.cursor = man["extra"]["cursor"]
        rec["restore_s"] = time.perf_counter() - t
        rec["resumed_step"] = self.cursor
        rec["resumed_loss"] = self.step()
        rec["recover_s"] = time.perf_counter() - t0
        self.recoveries.append(rec)
        return rec

    def close(self) -> None:
        """Stops the cluster's threads, without the final digest a
        process's close would run, and removes the cluster's root."""
        if self.cluster is not None:
            for ls in list(self.cluster.procs.values()):
                ls.chain.stop()
            for nid, sfs in self.cluster.sharedfs.items():
                if nid not in self.cluster.dead_nodes:
                    sfs.shutdown()
            self.cluster = self.store = self.ckpt = None
        if self.cluster_root and os.path.exists(self.cluster_root):
            shutil.rmtree(self.cluster_root, ignore_errors=True)
        if self._lock is not None:
            os.unlink(self._lock.name)
            self._lock.close()
            self._lock = None


def _named(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {weights.leaf_name(p): x for p, x in flat}
