"""Median host time of a resumed level's prefix work, in milliseconds:
the program's `dpf.prefixes` spans (validating the prefixes, mapping
them to tree nodes and leaf positions, staging the index arrays and
dispatching the frontier's gather), from the span log (spanlog.py).
Layer: hierarchy host. Host clock."""

import spanlog


def read(record):
    return spanlog.median_ms(record, "dpf.prefixes")
