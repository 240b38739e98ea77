"""The program's own spans, and the scopes of the device's ops, in the
profiler trace of one run's window.

The program times each stage of a save, a restore and a failover as an
interval span (``Tracer.span`` in ``repro.core.obs``), which it opens as
a ``jax.profiler.TraceAnnotation`` named ``assise.<stage>`` with the
stage's counts (bytes, leaves, entries) as the event's stats. Those
events lie on a host plane beside the benchmark's ``bench.*`` spans, on
the clock of the device ops, one line per host thread. The metadata of
each op event of a TPU's ``XLA Ops`` line holds, in its ``tf_op`` stat,
the ``jax.named_scope`` path of the code that made the op
(``jit(step_fn)/transpose(jvp(wkv))/while/body/...``); a loop's own
event has none, its body's events lie inside it. ``ProfileData`` gives
an event its own stats only, so the scopes are read from the XSpace
protobuf itself.

``trace.load`` keeps only the ``bench.*`` spans, so this module reads
the run's .xplane.pb a second time: ``of(run)`` finds the file that the
harness wrote (a ``chipbench-trace-*`` directory in the temporary
directory) by its window.

  program_spans   host events named ``assise.*``: name (prefix kept),
                  start, end, thread, stats;
  host_events     every other host event that lasts (bench.* spans and
                  the runtime's own, such as PjRt's);
  scoped_ops      {chip: [(scope path, start, end)]} of the op events.
"""
from __future__ import annotations

import functools
import glob
import importlib.util
import os
import re
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace as tracemod

PREFIX = "assise."
SCOPE_STAT = "tf_op"  # the op metadata's stat that holds its scope path
TRACE_DIRS = "chipbench-trace-*"

Interval = Tuple[float, float]


@dataclass
class HostEvent:
    name: str
    start: float  # ns
    end: float
    thread: int  # the index of its line among the host planes' lines
    stats: Dict[str, object] = field(default_factory=dict)


def _union(ivs: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class ProgramTrace:
    window: Interval
    program_spans: List[HostEvent]
    host_events: List[HostEvent] = field(default_factory=list)
    scoped_ops: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)

    def _clip(self, s: float, e: float) -> Interval:
        return max(s, self.window[0]), min(e, self.window[1])

    def spans(self, name: str, thread: Optional[int] = None
              ) -> List[HostEvent]:
        """The ``assise.<name>`` spans, on one thread if given."""
        full = PREFIX + name
        return [ev for ev in self.program_spans if ev.name == full
                and (thread is None or ev.thread == thread)]

    def span_seconds(self, name: str, thread: Optional[int] = None
                     ) -> float:
        """Seconds of the ``assise.<name>`` spans inside the window."""
        total = 0.0
        for ev in self.spans(name, thread):
            s, e = self._clip(ev.start, ev.end)
            total += max(0.0, e - s)
        return total / 1e9

    def thread_of(self, name: str) -> Optional[int]:
        """The thread that runs the ``assise.<name>`` spans (the first
        one's), or None where there is none."""
        evs = self.spans(name)
        return evs[0].thread if evs else None

    def self_segments(self) -> List[Tuple[str, float, float]]:
        """Each instant of a program span, given to the innermost span
        open then on its thread: (name, start, end) pieces. A span's
        self time is the sum of its pieces."""
        out = []
        by_thread: Dict[int, List[HostEvent]] = {}
        for ev in self.program_spans:
            by_thread.setdefault(ev.thread, []).append(ev)
        for evs in by_thread.values():
            evs.sort(key=lambda ev: (ev.start, -ev.end))
            stack: List[list] = []  # [event, time it resumes from]

            def close_until(t):
                while stack and stack[-1][0].end <= t:
                    ev, at = stack.pop()
                    if ev.end > at:
                        out.append((ev.name, at, ev.end))
                    if stack:
                        stack[-1][1] = ev.end

            for ev in evs:
                close_until(ev.start)
                if stack:
                    top = stack[-1]
                    if ev.start > top[1]:
                        out.append((top[0].name, top[1], ev.start))
                    top[1] = ev.end
                stack.append([ev, ev.start])
            close_until(float("inf"))
        return out

    def self_seconds(self, name: str) -> float:
        """Self time of the ``assise.<name>`` spans: their duration less
        what the spans opened inside them on the same thread cover."""
        full = PREFIX + name
        return sum(e - s for n, s, e in self.self_segments()
                   if n == full) / 1e9

    def scope_seconds(self, scope: str, chip: int = 0) -> float:
        """Device seconds inside the window in which an op made under
        the ``jax.named_scope`` ``scope`` ran on ``chip`` (the union of
        their intervals: a loop's event holds its body's). The scope is
        found as a whole word of the path, also inside a transformation
        such as ``transpose(jvp(wkv))``."""
        word = re.compile(r"(?<![\w.-])" + re.escape(scope) + r"(?![\w.-])")
        ivs = [self._clip(s, e) for path, s, e in
               self.scoped_ops.get(chip, ()) if word.search(path)]
        return sum(e - s for s, e in _union(ivs)) / 1e9

    def gap_spans(self, trace: tracemod.Trace, top: int = 5,
                  per_gap: int = 4) -> List[dict]:
        """For each of the ``top`` longest idle gaps of chip 0 (longest
        first): its start (seconds into the window), its length, and
        the host work that overlaps it most, each with its overlap in
        seconds: program spans by self time first (``spans``), then the
        runtime's events (``runtime``)."""
        if not trace.device_ops:
            return []
        busy = trace.busy_intervals(min(trace.device_ops))
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        pieces = self.self_segments()
        runtime = [ev for ev in self.host_events
                   if not ev.name.startswith("bench.")]
        out = []
        for s, e in gaps[:top]:
            prog: Dict[str, float] = {}
            for name, a, b in pieces:
                ov = min(e, b) - max(s, a)
                if ov > 0:
                    prog[name] = prog.get(name, 0.0) + ov
            rt: Dict[str, float] = {}
            for ev in runtime:
                ov = min(e, ev.end) - max(s, ev.start)
                if ov > 0:
                    rt[ev.name] = rt.get(ev.name, 0.0) + ov
            out.append({
                "at_s": (s - self.window[0]) / 1e9, "gap_s": (e - s) / 1e9,
                "spans": [[n, v / 1e9] for n, v in
                          sorted(prog.items(), key=lambda kv: -kv[1])
                          [:per_gap]],
                "runtime": [[n, v / 1e9] for n, v in
                            sorted(rt.items(), key=lambda kv: -kv[1])
                            [:per_gap]]})
        return out


@functools.lru_cache(maxsize=None)
def xplane_pb2():
    """The XSpace protobuf classes, from the copy of
    ``tsl/profiler/protobuf/xplane.proto`` that TensorFlow installs,
    loaded without importing TensorFlow."""
    tf = importlib.util.find_spec("tensorflow")
    if tf is None or not tf.submodule_search_locations:
        raise ImportError("reading op scopes needs TensorFlow's xplane_pb2")
    path = os.path.join(tf.submodule_search_locations[0], "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("chipbench_xplane_pb2",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def op_scopes(path: str) -> Dict[int, List[Tuple[str, float, float]]]:
    """{chip: [(scope path, start ns, end ns)]} of the op events of each
    TPU plane that carry a scope, read from the .xplane.pb at ``path``."""
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[int, list] = {}
    for plane in space.planes:
        tail = plane.name[len(tracemod.DEVICE_PREFIX):]
        if not plane.name.startswith(tracemod.DEVICE_PREFIX) \
                or not tail.isdigit():
            continue
        key = next((k for k, m in plane.stat_metadata.items()
                    if m.name == SCOPE_STAT), None)
        scope = {}
        for mid, md in plane.event_metadata.items():
            for st in md.stats:
                if st.metadata_id == key:
                    scope[mid] = st.str_value or \
                        plane.stat_metadata[st.ref_value].name
        ops = out.setdefault(int(tail), [])
        for line in plane.lines:
            if line.name != tracemod.OPS_LINE:
                continue
            for ev in line.events:
                if ev.metadata_id in scope:
                    start = line.timestamp_ns + ev.offset_ps / 1000
                    ops.append((scope[ev.metadata_id], start,
                                start + ev.duration_ps / 1000))
    return out


def load(path: str) -> ProgramTrace:
    """Reads an .xplane.pb file (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = tracemod.find_xplane(path)
    data = ProfileData.from_file(path)
    window = None
    prog: List[HostEvent] = []
    host: List[HostEvent] = []
    thread = 0
    for plane in data.planes:
        if plane.name.startswith(tracemod.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == tracemod.WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                    continue
                if not e.duration_ns:
                    continue
                ev = HostEvent(e.name, e.start_ns,
                               e.start_ns + e.duration_ns, thread)
                if e.name.startswith(PREFIX):
                    ev.stats = dict(e.stats)
                    prog.append(ev)
                else:
                    host.append(ev)
            thread += 1
    if window is None:
        raise ValueError(f"{path}: no {tracemod.WINDOW} span")
    return ProgramTrace(window=window, program_spans=prog, host_events=host,
                        scoped_ops=op_scopes(path))


def find(window: Interval, root: Optional[str] = None
         ) -> Optional[ProgramTrace]:
    """The program trace under ``root`` (default: the temporary
    directory's ``chipbench-trace-*`` directories, newest first) whose
    window is ``window``."""
    dirs = sorted(glob.glob(os.path.join(root or tempfile.gettempdir(),
                                         TRACE_DIRS)),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        try:
            pt = load(d)
        except (FileNotFoundError, ValueError):
            continue
        if pt.window == window:
            return pt
    return None


def of(run) -> Optional[ProgramTrace]:
    """The program trace of a traced run, or None (no trace, or its file
    is not found), kept on the run as ``program_trace`` for the next
    reader."""
    if run.trace is None:
        return None
    if "program_trace" not in vars(run):
        run.program_trace = find(run.trace.window)
    return run.program_trace


def per_save(run, name: str, on_saving_thread: bool = False
             ) -> Optional[float]:
    """Seconds of the window's ``assise.<name>`` spans per save of the
    window, only those on the thread that runs ``assise.ckpt.save``
    where ``on_saving_thread``; None without a trace or a save. A
    program that emits no such span reads 0."""
    pt = of(run)
    if pt is None or not run.saves:
        return None
    thread = pt.thread_of("ckpt.save") if on_saving_thread else None
    if on_saving_thread and thread is None:
        return 0.0
    return pt.span_seconds(name, thread) / len(run.saves)
