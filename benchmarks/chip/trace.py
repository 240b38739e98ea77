"""Reduction of a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run on that chip, their ``XLA Modules``
line one per jitted program run. The benchmark's own host spans
(``jax.profiler.TraceAnnotation``, named ``bench.*``) sit on a host
plane, on the same clock. ``bench.window`` spans the measured window.

  busy_s       union of the op intervals inside the window, averaged
               over the chips;
  ops          {op name: device seconds inside the window}, all chips;
  modules      {program name: (runs, device seconds)} inside the window;
  idle_gaps    the longest gaps between busy intervals, each named by
               the host span that overlaps it most.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]  # start, end in ns


@dataclass
class Trace:
    window: Interval
    device_ops: Dict[int, List[Tuple[str, float, float]]]  # chip -> ops
    modules: List[Tuple[str, float, float]]
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clip(self, start: float, end: float) -> Interval:
        return max(start, self.window[0]), min(end, self.window[1])

    def busy_intervals(self, chip: int) -> List[Interval]:
        ivs = sorted(self._clip(s, e) for _, s, e in self.device_ops[chip])
        merged: List[List[float]] = []
        for s, e in ivs:
            if e <= s:
                continue
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    @property
    def busy_s(self) -> float:
        chips = sorted(self.device_ops)
        if not chips:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(c))
                   for c in chips) / len(chips) / 1e9

    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for ops in self.device_ops.values():
            for name, s, e in ops:
                s, e = self._clip(s, e)
                if e > s:
                    out[name] += (e - s) / 1e9
        return dict(out)

    def module_runs(self, needle: str) -> Tuple[int, float]:
        """(runs, device seconds) of the programs whose name holds
        ``needle``, inside the window."""
        runs, secs = 0, 0.0
        for name, s, e in self.modules:
            if needle in name:
                s, e = self._clip(s, e)
                if e > s:
                    runs += 1
                    secs += (e - s) / 1e9
        return runs, secs

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` longest idle gaps of chip 0, longest first, each
        named by the bench.* span that overlaps it most."""
        if not self.device_ops:
            return []
        busy = self.busy_intervals(min(self.device_ops))
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, overlap = "host", 0.0
            for name, hs, he in self.host_spans:
                ov = min(e, he) - max(s, hs)
                if ov > overlap:
                    best, overlap = name, ov
            out.append((best, (e - s) / 1e9))
        return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Reads an .xplane.pb file (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    window = None
    spans: List[Tuple[str, float, float]] = []
    ops: Dict[int, list] = {}
    modules: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            tail = plane.name[len(DEVICE_PREFIX):]
            if not tail.isdigit():
                continue
            chip = int(tail)
            ops.setdefault(chip, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip].extend((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                                     for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("bench."):
                    continue
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == WINDOW:
                    window = iv
                else:
                    spans.append((e.name, *iv))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW} span")
    return Trace(window=window, device_ops=ops, modules=modules,
                 host_spans=spans)
