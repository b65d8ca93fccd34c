"""Read what `jax.profiler.ProfileData` does not expose from a profiler
trace (`.xplane.pb`), and lay the program's span log over it.

* Program scopes. On a TPU trace an op event's name is its bare HLO
  text; the op's scope path (`jit(run)/pir.expand/add`, from the
  `jax.named_scope` names of the program) is the `tf_op` stat
  (`path:type`) of the op's *event metadata*, which `ProfileData` does
  not list. A small decode of the protobuf wire format reads the device
  planes' `event_metadata`, `stat_metadata` and `XLA Ops` line.
* Clock. Host events in a trace sit on the profiler's clock; the span
  log sits on `time.perf_counter_ns()`. A window mark that records
  `perf_counter_ns()` inside its `harness.window` annotation is seen on
  both: the start mark gives the offset from log time to trace time,
  the end mark the drift.
* Idle attribution. Each idle gap of the device goes to the innermost
  logged span over it (one that covers at least half of the gap, so one
  that holds its midpoint).

Only the device planes are decoded here; host events come from
`ProfileData` (trace_reduce.load). What trace_reduce.py computes is not
touched.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import heapq
import re
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce

TF_OP = "tf_op"
_OP_TYPE = re.compile(r":[^/]*$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

# XSpace / XPlane / XLine / XEvent / XStat field numbers (xplane.proto).
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_META_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, i: int = 0, end: Optional[int] = None):
    """(field number, value) of one message in `buf[i:end]`: an int for
    varints, an (start, end) slice for length-delimited fields; fixed
    width fields are skipped."""
    end = len(buf) if end is None else end
    while i < end:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, value = 0, None
    for field, v in _fields(buf, *span):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Tuple[float, float, int]]  # (start_ns, end_ns, metadata id)
    scopes: Dict[int, str]  # metadata id -> tf_op path ("" when none)


def _plane(buf, span) -> Optional[DevicePlane]:
    name, lines, event_meta, stat_meta = "", [], [], {}
    for field, v in _fields(buf, *span):
        if field == _PLANE_NAME:
            name = _text(buf, v)
            if not _DEVICE_PLANE.match(name):
                return None
        elif field == _PLANE_LINES:
            lines.append(v)
        elif field == _PLANE_EVENT_META:
            event_meta.append(v)
        elif field == _PLANE_STAT_META:
            key, value = _map_entry(buf, v)
            for f, s in _fields(buf, *value):
                if f == _META_NAME:
                    stat_meta[key] = _text(buf, s)
    if not name:
        return None
    tf_op = {k for k, v in stat_meta.items() if v == TF_OP}
    scopes = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        scope = ""
        for f, v in _fields(buf, *value):
            if f == _META_STATS:
                stat_id, text = None, None
                for sf, sv in _fields(buf, *v):
                    if sf == _STAT_META_ID:
                        stat_id = sv
                    elif sf == _STAT_STR:
                        text = _text(buf, sv)
                    elif sf == _STAT_REF:
                        text = stat_meta.get(sv, "")
                if stat_id in tf_op and text is not None:
                    # `op_name:op_type`; the type may be empty.
                    scope = _OP_TYPE.sub("", text)
        scopes[key] = scope
    ops = []
    for line in lines:
        line_name, t0, events = "", 0, []
        for field, v in _fields(buf, *line):
            if field == _LINE_NAME:
                line_name = _text(buf, v)
            elif field == _LINE_TIMESTAMP_NS:
                t0 = v
            elif field == _LINE_EVENTS:
                events.append(v)
        if line_name != trace_reduce.OPS_LINE:
            continue
        for event in events:
            meta_id = offset = duration = 0
            for f, v in _fields(buf, *event):
                if f == _EVENT_META_ID:
                    meta_id = v
                elif f == _EVENT_OFFSET_PS:
                    offset = v
                elif f == _EVENT_DURATION_PS:
                    duration = v
            start = t0 + offset / 1e3
            ops.append((start, start + duration / 1e3, meta_id))
    return DevicePlane(name, ops, scopes)


def read_device_planes(data: bytes) -> List[DevicePlane]:
    """The TPU device planes of a serialized XSpace."""
    buf = memoryview(data)
    planes = []
    for field, v in _fields(buf):
        if field == _SPACE_PLANES:
            plane = _plane(buf, v)
            if plane is not None:
                planes.append(plane)
    return planes


def in_scope(path: str, scope: str) -> bool:
    """Whether the scope path `jit(f)/a/b/op` runs under scope `scope`
    (one of its `/`-separated components)."""
    return scope in path.split("/")


class Scopes:
    """Device time by program scope over a window of a trace."""

    def __init__(self, planes: List[DevicePlane]):
        if not planes:
            raise ValueError("the trace holds no TPU device plane")
        self.planes = planes

    @classmethod
    def from_file(cls, path: str) -> "Scopes":
        with open(path, "rb") as f:
            return cls(read_device_planes(f.read()))

    def _clipped(self, window):
        lo, hi = window if window is not None else (float("-inf"), float("inf"))
        for plane in self.planes:
            for s, e, meta in plane.ops:
                if e > lo and s < hi:
                    yield plane, min(e, hi) - max(s, lo), meta

    def scope_ns(self, scope: str, window=None) -> float:
        """Device ns, summed over devices, of the ops under `scope`."""
        return sum(
            ns for plane, ns, meta in self._clipped(window)
            if in_scope(plane.scopes.get(meta, ""), scope)
        )

    def totals(self, window=None, depth: int = 2) -> Dict[str, float]:
        """Device ns by the first `depth` components of the scope path
        ("" for ops with no `tf_op`)."""
        out: Dict[str, float] = collections.defaultdict(float)
        for plane, ns, meta in self._clipped(window):
            path = plane.scopes.get(meta, "")
            out["/".join(path.split("/")[:depth])] += ns
        return dict(out)

    def busy(self, window) -> List[List[Tuple[float, float]]]:
        """Per device, the union of op intervals clipped to `window`."""
        lo, hi = window
        return [
            trace_reduce.union(
                trace_reduce.clip([(s, e) for s, e, _ in p.ops], lo, hi)
            )
            for p in self.planes
        ]

    def gaps(self, window) -> List[Tuple[float, float]]:
        """Every idle interval of every device inside `window`."""
        lo, hi = window
        out = []
        for busy in self.busy(window):
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            out.extend(
                (g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0
            )
        return out


# ---------------------------------------------------------------------------
# The span log on the trace's clock
# ---------------------------------------------------------------------------


def host_events(profile, names: Optional[Iterable[str]] = None):
    """{name: sorted [(start_ns, end_ns)]} of the host events (all, or
    those named in `names`) of a `ProfileData`."""
    wanted = set(names) if names is not None else None
    out: Dict[str, list] = collections.defaultdict(list)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if wanted is None or e.name in wanted:
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    for events in out.values():
        events.sort()
    return dict(out)


def window_marks(profile) -> List[Tuple[float, float]]:
    """The `harness.window` annotations, in order, on the trace clock."""
    return host_events(profile, [trace_reduce.WINDOW_ANNOTATION]).get(
        trace_reduce.WINDOW_ANNOTATION, []
    )


def clock_offsets(marks_trace, marks_log_ns) -> List[float]:
    """Trace time minus log time at each mark: a mark's log time was
    taken inside its (instant) annotation, whose middle it is set
    against. The first offset maps the log; the others show drift."""
    if len(marks_trace) != len(marks_log_ns) or not marks_log_ns:
        raise ValueError(
            f"{len(marks_trace)} marks in the trace, {len(marks_log_ns)} "
            "in the log"
        )
    return [(s + e) / 2 - t for (s, e), t in zip(marks_trace, marks_log_ns)]


def annotation_agreement(records, annotations, offset: float,
                         tolerance_ns: float = 100_000.0):
    """(matched, compared): logged spans whose name is also a host
    annotation in the trace, and how many of them start within
    `tolerance_ns` of an annotation of that name once moved by
    `offset` onto the trace clock."""
    starts = {name: [s for s, _ in ev] for name, ev in annotations.items()}
    lo = min((ev[0][0] for ev in annotations.values() if ev), default=None)
    hi = max((ev[-1][1] for ev in annotations.values() if ev), default=None)
    matched = compared = 0
    for r in records:
        ann = starts.get(r["name"])
        if not ann:
            continue
        t = r["start_ns"] + offset
        if t < lo or t > hi:
            continue  # outside the captured part of the trace
        compared += 1
        i = bisect.bisect_left(ann, t)
        near = min(abs(ann[j] - t) for j in (i - 1, i) if 0 <= j < len(ann))
        matched += near <= tolerance_ns
    return matched, compared


def attribute_idle(gaps, records, offset: float):
    """(attributed ns, idle ns, {span name: ns}): each gap goes to the
    innermost (shortest) logged span that holds its midpoint, i.e. one
    that covers at least half of it; gaps under no span stay
    unattributed (`{None: ns}` in the breakdown). One sweep over the
    midpoints in time order, with the open spans in a heap by length."""
    spans = sorted(
        (r["start_ns"] + offset, r["end_ns"] + offset, r["name"])
        for r in records
    )
    open_spans: list = []  # (length, end, name)
    by_name: Dict[Optional[str], float] = collections.defaultdict(float)
    idle = attributed = 0.0
    i = 0
    for g0, g1 in sorted(gaps, key=lambda g: g[0] + g[1]):
        width, mid = g1 - g0, (g0 + g1) / 2
        idle += width
        while i < len(spans) and spans[i][0] <= mid:
            s, e, name = spans[i]
            heapq.heappush(open_spans, (e - s, e, name))
            i += 1
        # Midpoints only grow: a span that ended before this one never
        # holds a later one, so it leaves the heap for good.
        while open_spans and open_spans[0][1] < mid:
            heapq.heappop(open_spans)
        if open_spans:
            by_name[open_spans[0][2]] += width
            attributed += width
        else:
            by_name[None] += width
    return attributed, idle, dict(by_name)
