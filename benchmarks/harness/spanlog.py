"""The program's span log (`observability/tracing.py`: `span_log`) as the
per-layer metrics read it.

A run's record may carry `spans`: the drained log of a run that started
it when the window started (`spans_run.py`), which covers the whole
window. Without that key a reader takes what the program's log holds
in this process: the log records while the profiler captures, so after
a `--trace 1` run that is the traced part of the window, and the reader
keeps the spans that ended inside the traced span, read beside the
device trace of that span (a run whose trace held no device gives
nothing). A program without a span log, or a log that dropped records,
gives nothing: a metric then reports no value.

Device metrics also read `xplane` (the path of the raw trace) and
`marks_ns` (the log's clock at each window mark), which only
`spans_run.py` records so far.
"""

from __future__ import annotations

import functools
import statistics
from typing import List, Optional


def _program_log() -> Optional[dict]:
    try:
        from distributed_point_functions_tpu.observability import tracing
    except ImportError:
        return None
    span_log = getattr(tracing, "span_log", None)
    return span_log.snapshot() if span_log is not None else None


def records(record: dict) -> Optional[List[dict]]:
    """The span records a metric reads, or None (see module doc)."""
    log = record.get("spans")
    whole_window = log is not None
    if log is None and record.get("trace") is not None:
        log = _program_log()
    if log is None or log.get("dropped"):
        return None
    out = log["records"]
    traced = record.get("traced") or {}
    if not whole_window and "t_start" in traced and "t_stop" in traced:
        lo, hi = traced["t_start"] * 1e9, traced["t_stop"] * 1e9
        out = [r for r in out if lo <= r["end_ns"] <= hi]
    return out or None


def durations_ms(spans: List[dict], name: str) -> List[float]:
    return [(r["end_ns"] - r["start_ns"]) / 1e6 for r in spans
            if r["name"] == name]


def median_ms(record: dict, name: str) -> Optional[float]:
    spans = records(record)
    values = durations_ms(spans, name) if spans else []
    return statistics.median(values) if values else None


def helper_host_ms(spans: List[dict]) -> List[float]:
    """Per `helper.request` root: its time less that of the batcher
    waits directly under it (`batcher.submit`, same trace and thread):
    the Helper's host work, i.e. decode, decrypt, mask and encode."""
    waits = {}
    for r in spans:
        if r["name"] == "batcher.submit" and r["parent"] == "helper.request":
            key = (r["trace_id"], r["thread"])
            waits[key] = waits.get(key, 0) + r["end_ns"] - r["start_ns"]
    return [
        (r["end_ns"] - r["start_ns"]
         - waits.get((r["trace_id"], r["thread"]), 0)) / 1e6
        for r in spans if r["name"] == "helper.request"
    ]


@functools.lru_cache(maxsize=2)
def _scopes(path: str):
    import xplane_scopes

    return xplane_scopes.Scopes.from_file(path)


@functools.lru_cache(maxsize=2)
def _profile(path: str):
    import trace_reduce

    return trace_reduce.load(path)


@functools.lru_cache(maxsize=2)
def _marks(path: str):
    import xplane_scopes

    return xplane_scopes.window_marks(_profile(path))


def scopes(record: dict):
    """The scope reader over the run's raw trace, or None."""
    path = record.get("xplane")
    return _scopes(path) if path else None


def window(record: dict):
    """The traced window on the trace clock: first to last mark."""
    path = record.get("xplane")
    marks = _marks(path) if path else None
    return (marks[0][0], marks[-1][1]) if marks else None


def clock(record: dict) -> Optional[dict]:
    """{offset_ns, drift_ns}: trace time minus log time at the start
    mark, and how far the end mark's offset moved from it."""
    import xplane_scopes

    path, marks_ns = record.get("xplane"), record.get("marks_ns")
    if not path or not marks_ns:
        return None
    offsets = xplane_scopes.clock_offsets(_marks(path), marks_ns)
    return {"offset_ns": offsets[0], "drift_ns": offsets[-1] - offsets[0]}


def quiet_intervals(spans: List[dict], top: int = 5) -> List[dict]:
    """The `top` longest intervals in which no span started or ended
    (the interpreter stalled, or nothing ran), each with the spans
    open across it, counted by name."""
    edges = sorted({t for r in spans for t in (r["start_ns"], r["end_ns"])})
    longest = sorted(zip(edges, edges[1:]), key=lambda g: g[0] - g[1])[:top]
    out = []
    for t0, t1 in longest:
        mid = (t0 + t1) / 2
        open_names: dict = {}
        for r in spans:
            if r["start_ns"] <= mid <= r["end_ns"]:
                open_names[r["name"]] = open_names.get(r["name"], 0) + 1
        out.append({"start_ns": t0, "ms": (t1 - t0) / 1e6, "open": open_names})
    return out


def profile(record: dict):
    path = record.get("xplane")
    return _profile(path) if path else None
