"""One run of one cell: set-up, the measured window, the checks that
decide ``correct``, and the result line.

Set-up builds the job (weights from the seed on the device, the train
step compiled from the persistent cache), runs its first three steps
(what the training check compares), and, where the traffic saves or
restores, opens the cluster, makes the traffic's set-up saves, compiles
the changed-block kernel for this state's leaf sizes and drains the
store of background digests. The window then runs the traffic's script
of operations for ``--seconds``; nothing may compile inside it. After
it: the store is drained (what the window's save wrote, background
digests included, is counted), device memory is read, every leaf the
window saved is read back from each replica, the job's state is freed,
and the plain reference replays the first three steps.
"""
from __future__ import annotations

import gc
import io
import json
import os
import resource
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from benchmarks.chip import check, spec, trace as tracemod, weights
from benchmarks.chip.job import Job

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


@dataclass
class Run:
    """What a metric reader reads of one run."""
    cell: dict
    cfg: dict
    traffic: dict
    job: Job
    chips: int
    peak: Dict[str, float]
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    saves: List[dict] = field(default_factory=list)  # the window's saves
    recoveries: List[dict] = field(default_factory=list)
    window_write_bytes: Optional[int] = None
    trace: Optional[tracemod.Trace] = None

    @property
    def tokens(self) -> int:
        return self.steps * self.job.tokens_per_step


class CompileCounter:
    """Counts traces, compiles and compile-cache loads while active."""

    def __init__(self):
        self.count = 0

    def _on(self, name, *_, **__):
        if name in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_listener(self._on)
        jax.monitoring.unregister_event_duration_listener(self._on)


def io_counters() -> Dict[str, int]:
    """This process's /proc/self/io: wchar counts every byte handed to
    write(2) and its kin, write_bytes what reached a block device."""
    out = {}
    with open("/proc/self/io") as f:
        for line in f:
            k, v = line.split(":")
            out[k.strip()] = int(v)
    return out


def drive_window(job: Job, script: List[dict], seconds: float):
    """Runs the traffic's operations; ``steps`` without ``n`` runs steps
    until ``seconds`` have passed. Returns (steps, operations, seconds)."""
    t0 = time.perf_counter()
    end = t0 + seconds
    steps = ops = 0
    for op in script:
        kind = op["op"]
        if kind == "steps":
            n, i = op.get("n"), 0
            while (i < n) if n is not None else time.perf_counter() < end:
                job.step()
                i += 1
            steps += i
            ops += i
        elif kind == "save":
            job.save()
            ops += 1
        elif kind == "kill":
            job.kill_and_resume()
            steps += 1  # the first resumed step
            ops += 1
        else:
            raise ValueError(f"unknown window operation {kind!r}")
    return steps, ops, time.perf_counter() - t0


def tree_bytes(root: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(root) for f in files)


# -- checks ------------------------------------------------------------------
def readback(job: Job, save: dict) -> int:
    """Reads every leaf of ``save`` back from each replica in the chain
    (the writer's node through its process, the others from their own
    tiers) and counts (replica, leaf) pairs that are missing or differ
    from the state handed to ``save``."""
    cl = job.cluster
    prefix = job.ckpt_cfg.prefix
    step, fps = save["step"], save["fingerprints"]
    writer = job.store.sfs.node_id
    readers = {writer: job.store.get}
    for nid in cl.cm.chain_for("/x") + cl.cm.reserves.get("/", []):
        if nid != writer and nid not in cl.dead_nodes:
            readers[nid] = lambda k, s=cl.sharedfs[nid]: s.read_any(k)[1]
    bad = 0
    for get in readers.values():
        man = get(f"{prefix}/MANIFEST.{step}")
        man = json.loads(man) if man is not None else {"leaves": []}
        if man.get("step") != step or set(man["leaves"]) != set(fps):
            bad += len(fps)
            continue
        for name, fp in fps.items():
            key = (f"{prefix}/data{name}" if man["format"] == "range"
                   else f"{prefix}/data/{step}{name}")
            raw = get(key)
            if raw is None:
                bad += 1
                continue
            arr = np.load(io.BytesIO(raw), allow_pickle=False)
            bad += check.host_fingerprint(arr) != fp
    return bad


def recovery_numbers(job: Job, rec: dict, saved: dict) -> Dict[str, float]:
    """The restored state against the state that was saved, leaf by
    leaf; the resumed steps' losses against the same steps' losses
    before the kill, bit for bit."""
    got = {n: check.host_fingerprint(a)
           for n, a in rec.pop("restored").items()}
    mism = sum(got.get(n) != fp for n, fp in saved["fingerprints"].items())
    mism += len(set(got) - set(saved["fingerprints"]))
    before = {}
    for step, loss in job.loss_log[:rec["log_at_kill"]]:
        before.setdefault(step, loss)
    resumed = job.loss_log[rec["log_at_kill"]:rec["log_at_kill"] + 2]
    gaps = [abs(loss - before[step]) for step, loss in resumed
            if step in before]
    return {"restore_mismatch": float(mism),
            "resume_loss_gap": max(gaps) if gaps else float("inf")}


# -- one run -----------------------------------------------------------------
def run_cell(bench: spec.Bench, name: str, seed: int, seconds: float,
             use_trace: bool, *, device: dict, t_start: float,
             run_config: Optional[dict] = None,
             store_dir: Optional[str] = None, say=print):
    """Returns (result line as a dict, {check: (value, limit)}).
    ``store_dir`` overrides where the cluster lives (``job.STORE_ROOT``)
    and lifts its need for a memory-backed filesystem."""
    cell = bench.workload(name)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    peak = spec.peaks(device["kind"])
    script = traffic["window"]
    uses_store = traffic["setup_saves"] > 0 or any(
        op["op"] in ("save", "kill") for op in script)
    store = ({"store_root": store_dir, "memory_fs": False} if store_dir
             else {})
    job = Job(cfg, traffic, seed, run_config=run_config, **store)
    run = Run(cell=cell, cfg=cfg, traffic=traffic, job=job,
              chips=cell["chips"], peak=peak)
    trace_dir = None
    try:
        job.build()
        job.run_first_steps(3)
        if uses_store:
            t = time.perf_counter()
            job.open_store()
            for _ in range(traffic["setup_saves"]):
                job.save()
            job.warm_kernel()
            job.drain()
            job.phase["setup_save_s"] = time.perf_counter() - t
            say(f"cluster root: {job.cluster_root} ({job.store_fs})")
        n_setup_saves = len(job.save_log)
        gc.collect()
        run.setup_s = time.perf_counter() - t_start
        say("set-up seconds: " + ", ".join(
            f"{k} {v:.3f}" for k, v in job.phase.items())
            + f"; total {run.setup_s:.3f}")
        say(f"step plan {job.plan_bytes} bytes of device memory")

        io0 = io_counters()["wchar"]
        if use_trace:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with CompileCounter() as compiles:
            with jax.profiler.TraceAnnotation(tracemod.WINDOW):
                run.steps, attempted, run.window_s = drive_window(
                    job, script, seconds)
        if use_trace:
            jax.profiler.stop_trace()
            run.trace = tracemod.load(trace_dir)
        say(f"window: {run.window_s:.3f} s, {run.steps} steps, "
            f"{attempted} operations; compilations inside it: "
            f"{compiles.count}")
        if compiles.count:
            raise RuntimeError(f"{compiles.count} compilations inside the "
                               "window")
        run.saves = job.save_log[n_setup_saves:]
        run.recoveries = job.recoveries
        if uses_store:
            job.drain()
            run.window_write_bytes = io_counters()["wchar"] - io0
            say(f"cluster root holds {tree_bytes(job.cluster_root)} bytes")
        stats = jax.local_devices()[0].memory_stats() or {}
        device = dict(device,
                      memory_peak_bytes=int(stats.get("peak_bytes_in_use",
                                                      0)))

        limits = cfg["limits"]
        numbers: Dict[str, float] = {}
        if run.saves:
            numbers["readback_mismatch"] = float(sum(
                readback(job, s) for s in run.saves))
        for rec in run.recoveries:
            saved = next(s for s in job.save_log
                         if s["step"] == rec["saved_step"])
            for k, v in recovery_numbers(job, rec, saved).items():
                numbers[k] = max(numbers.get(k, 0.0), v)

        metrics = {}
        kind = "per_layer" if use_trace else "end_to_end"
        for m in bench.metrics_for(name, kind):
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif kind == "end_to_end" or name in m.get("workloads", []):
                raise RuntimeError(f"{m['name']} found nothing to read in "
                                   f"{name}, which it lists")
        if run.window_write_bytes is not None:
            say(f"window wrote {run.window_write_bytes} bytes "
                f"(wchar, background digests included)")
        io1 = io_counters()
        say(f"run wrote {io1['wchar']} bytes (wchar), {io1['write_bytes']} "
            f"to block devices; host peak RSS "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}"
            f" bytes")
        first = job.first_steps
        shapes = job.shapes
        job.close()
        job.params = job.opt = job.step_fn = None
        gc.collect()

        t = time.perf_counter()
        ref_mod = spec.reference(cfg["reference"]["module"])
        batches = [job._batch(i) for i in range(len(first["losses"]))]
        ref = ref_mod.train_steps(cfg, weights.make_params(shapes, seed),
                                  batches)
        numbers.update(check.training_numbers(first, ref))
        say(f"reference: {time.perf_counter() - t:.3f} s; losses "
            f"program {first['losses']} reference {ref['losses']}")
    finally:
        job.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    checks = {k: (v, limits[k]) for k, v in numbers.items()}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": attempted, "failed": 0, "metrics": metrics,
              "device": device}
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        ops = sorted(run.trace.op_seconds().items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ops[:10]],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps(10)]}
    if job.store_fs is not None:
        result["store"] = {"root": job.cluster_root, "fs": job.store_fs}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks
