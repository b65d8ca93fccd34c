"""Median host time of one resumed hierarchy level's call, in
milliseconds: the program's `dpf.evaluate_until` spans with attribute
`level` above 0 (prefix mapping, the frontier and the dispatch of the
level's program; the device runs it after the call returns), from the
span log (spanlog.py). Layer: hierarchy host. Host clock."""

import statistics

import spanlog


def read(record):
    spans = spanlog.records(record) or []
    values = [
        (r["end_ns"] - r["start_ns"]) / 1e6 for r in spans
        if r["name"] == "dpf.evaluate_until"
        and ((r["attrs"] or {}).get("level") or 0) > 0
    ]
    return statistics.median(values) if values else None
