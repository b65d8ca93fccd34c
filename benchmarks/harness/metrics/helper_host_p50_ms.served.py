"""Median host time of the Helper's requests: each `helper.request` root
span less the batcher waits directly under it (`batcher.submit`), so
what is left is the Helper's own host work: decode, decrypt, mask and
encode (span log, spanlog.py). Layer: sessions and wire. Host clock."""

import statistics

import spanlog


def read(record):
    spans = spanlog.records(record)
    values = spanlog.helper_host_ms(spans) if spans else []
    return statistics.median(values) if values else None
