"""Longest host-to-device copy of a batch's keys: the program's
`pir.stage_keys.put` spans (span log, spanlog.py), the one `device_put`
per batch in `pir/dense_eval.py:stage_keys`. A copy that waits on the
device, or holds the interpreter lock, shows here as one long span.
Layer: expansion. Host clock."""

import spanlog


def read(record):
    spans = spanlog.records(record)
    values = spanlog.durations_ms(spans, "pir.stage_keys.put") if spans else []
    return max(values) if values else None
