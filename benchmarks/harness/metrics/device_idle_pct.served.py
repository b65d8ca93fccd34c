"""Share of the traced window in which no operation ran on the device:
100 x (1 - union of the `XLA Ops` intervals / window), from the device
trace (trace_reduce.py), averaged over the devices. Layer: device."""


def read(record):
    trace = record.get("trace")
    if trace is None or trace.window_ns <= 0:
        return None
    return 100.0 * trace.idle_share
