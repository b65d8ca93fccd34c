"""Median host time of one full-domain evaluation's call, in
microseconds: the program's `dpf.evaluate_next` span (staging the key,
then dispatching the expansion and the leaf values; the device runs
them after it returns), from the span log (spanlog.py). The longest is
logged on stderr. Layer: device (what the host costs the device
between evaluations). Host clock."""

import sys

import spanlog


def read(record):
    spans = spanlog.records(record)
    values = spanlog.durations_ms(spans, "dpf.evaluate_next") if spans else []
    if not values:
        return None
    print(f"[metric] dpf.evaluate_next: max {max(values) * 1e3:.1f} us over "
          f"{len(values)} evaluations", file=sys.stderr, flush=True)
    return sorted(values)[len(values) // 2] * 1e3
