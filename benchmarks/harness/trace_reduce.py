"""Reduce a profiler trace (`.xplane.pb`) to what the metrics read.

Read with `jax.profiler.ProfileData`, nothing else. Three things come
out of a trace, all over one window:

* the union of the intervals in which an operation ran on each device
  (busy time), averaged over the devices;
* the device duration of every operation, summed by name, with the
  program (XLA module) each ran in;
* the longest idle gaps on the device, each labelled with the innermost
  host annotation (`TraceAnnotation`, program span or JAX event) that
  covers most of it.

Planes named `/device:TPU:<n>` are devices; their `XLA Ops` line holds
one event per operation and their `XLA Modules` line one event per
program run. Planes named `/host:...` hold the host threads. The window
runs from the first to the last host annotation named
`WINDOW_ANNOTATION` (the harness marks the start and the end of the
traced part of its window); without one, the trace's own extent.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

WINDOW_ANNOTATION = "harness.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class OpTotal:
    ns: float = 0.0
    count: int = 0
    module: str = ""


@dataclasses.dataclass
class Reduction:
    window_ns: float
    devices: int
    busy_ns: float  # union of op intervals, mean over devices
    ops: Dict[str, OpTotal]  # summed over devices
    modules: Dict[str, float]  # program name -> device ns, summed
    gaps: List[Tuple[float, str]]  # (ns, host label), the TOP longest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def top_ops(self, n: int = TOP) -> List[Tuple[str, float]]:
        ranked = sorted(self.ops.items(), key=lambda kv: -kv[1].ns)
        return [(name, t.ns / 1e9) for name, t in ranked[:n]]

    def top_gaps(self, n: int = TOP) -> List[Tuple[str, float]]:
        return [(label, ns / 1e9) for ns, label in self.gaps[:n]]

    def op_ns(self, pattern: str, field: str = "name") -> float:
        """Device ns of the ops whose name (or module, with
        `field="module"`) matches the regular expression `pattern`."""
        rx = re.compile(pattern)
        return sum(
            t.ns for name, t in self.ops.items()
            if rx.search(name if field == "name" else t.module)
        )


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping or touching [start, end) intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    ]


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _window(host_events, all_events) -> Tuple[float, float]:
    marks = [(s, e) for name, s, e in host_events if name == WINDOW_ANNOTATION]
    if marks:
        return min(s for s, _ in marks), max(e for _, e in marks)
    if not all_events:
        raise ValueError("the trace holds no events")
    return (min(s for _, s, _ in all_events), max(e for _, _, e in all_events))


def _label(gap: Tuple[float, float], host_events, window) -> str:
    """The innermost host event that covers at least half of the gap;
    failing that, the one that overlaps it most. Events spanning the
    whole window (the thread lifetimes) say nothing about the gap and
    are skipped."""
    g0, g1 = gap
    width = window[1] - window[0]
    inner, inner_len = None, None
    most, most_overlap = "no host activity", 0.0
    for name, s, e in host_events:
        if e - s >= width:
            continue
        overlap = min(e, g1) - max(s, g0)
        if overlap <= 0:
            continue
        if 2 * overlap >= g1 - g0 and (inner_len is None or e - s < inner_len):
            inner, inner_len = name, e - s
        if overlap > most_overlap:
            most, most_overlap = name, overlap
    return inner if inner is not None else most


def reduce(profile) -> Reduction:
    device_lines: Dict[str, Dict[str, list]] = {}
    host_events: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = device_lines.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend(_events(line))
    if not device_lines:
        raise ValueError("the trace holds no TPU device plane")
    device_events = [
        ev for lines in device_lines.values()
        for ev in lines.get(OPS_LINE, [])
    ]
    lo, hi = _window(host_events, device_events + host_events)
    window_ns = hi - lo
    busy_total = 0.0
    ops: Dict[str, OpTotal] = {}
    modules: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    for lines in device_lines.values():
        op_events = [(n, s, e) for n, s, e in lines.get(OPS_LINE, [])
                     if e > lo and s < hi]
        mod_events = sorted(
            (s, e, n) for n, s, e in lines.get(MODULES_LINE, [])
            if e > lo and s < hi
        )
        busy = union(clip([(s, e) for _, s, e in op_events], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in op_events:
            dur = min(e, hi) - max(s, lo)
            total = ops.setdefault(name, OpTotal())
            total.ns += dur
            total.count += 1
            if not total.module:
                total.module = _module_of(mod_events, s)
        for s, e, name in mod_events:
            modules[name] = modules.get(name, 0.0) + min(e, hi) - max(s, lo)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend(
            (g1 - g0, g0, g1)
            for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0
        )
    gaps.sort(key=lambda g: -g[0])
    return Reduction(
        window_ns=window_ns,
        devices=len(device_lines),
        busy_ns=busy_total / len(device_lines),
        ops=ops,
        modules=modules,
        gaps=[
            (ns, _label((g0, g1), host_events, (lo, hi)))
            for ns, g0, g1 in gaps[:TOP]
        ],
    )


def _module_of(mod_events, t: float) -> str:
    """Name of the program run (sorted (start, end, name)) holding t."""
    lo_i, hi_i = 0, len(mod_events)
    while lo_i < hi_i:
        mid = (lo_i + hi_i) // 2
        if mod_events[mid][0] <= t:
            lo_i = mid + 1
        else:
            hi_i = mid
    for s, e, name in reversed(mod_events[max(0, lo_i - 4):lo_i]):
        if s <= t < e:
            return name
    return ""


def reduce_file(path: str) -> Optional[Reduction]:
    return reduce(load(path))
