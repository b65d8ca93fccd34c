"""Share of the device's busy time spent under the program scope
`dpf.leaf_values` (`dpf.py:_leaf_stage`, the value hash and correction
of a full-domain evaluation), read from each op's scope in the raw
trace (xplane_scopes.py), over the busy time of the same window
(trace_reduce.py). The flattening reshape after it runs eagerly as its
own `jit(reshape)` program, and the leaves' reorder is the exit of the
expansion program (`dpf.expand`): neither is counted here. Layer:
expansion. Device trace."""

import spanlog


def read(record):
    scopes, window = spanlog.scopes(record), spanlog.window(record)
    trace = record.get("trace")
    if scopes is None or window is None or trace is None or trace.busy_ns <= 0:
        return None
    ns = scopes.scope_ns("dpf.leaf_values", window) / max(1, trace.devices)
    if ns <= 0:
        return None
    return 100.0 * ns / trace.busy_ns
