"""Device microseconds per evaluated key spent outside the inner
product: the device time of every operation in the window except those
of the inner-product program (module name below), over the keys both
parties' batchers evaluated while the trace ran (requests plus bucket
padding). This is the
DPF expansion and the layout glue around it (key staging, bit-reversal
and plane transposes), which the trace does not yet separate by name.
Layer: expansion (`pir/dense_eval_planes.py`,
`ops/expand_planes_pallas.py`).
"""

MODULE = r"ip_pallas_staged"


def read(record):
    trace = record.get("trace")
    batchers = (record.get("traced") or {}).get("counters")
    if trace is None or not batchers:
        return None
    keys = sum(
        party["requests_submitted"] + party["padded_keys"]
        for party in batchers.values()
    )
    total = sum(t.ns for t in trace.ops.values())
    outside = total - trace.op_ns(MODULE, field="module")
    if not keys or outside <= 0:
        return None
    return outside / 1e3 / keys
