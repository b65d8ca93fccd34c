"""Answered queries per second: the requests scheduled in the window
that were answered, over the time from the window's start to the last
answer (at least the window). Below capacity this is the offered rate;
above it, the rate the system sustains. Host clock."""


def read(record):
    window = record["window"]
    if "latencies_ms" not in window:
        return None
    return len(window["latencies_ms"]) / window["drain_s"]
