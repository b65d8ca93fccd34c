"""Median time a request waits in a batcher's queue, from submit to the
gate of the batch that takes it: the program's `batcher.queue_wait`
spans of both parties (span log, spanlog.py). Layer: batcher. Host
clock."""

import spanlog


def read(record):
    return spanlog.median_ms(record, "batcher.queue_wait")
