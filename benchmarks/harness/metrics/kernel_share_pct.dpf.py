"""Share of the device's busy time spent inside Pallas kernels during
full-domain evaluation; the rest is XLA glue (fusions, transposes,
reshapes, copies, the output assembly). From the device trace. Layer:
expansion (`dpf.py:_expand_levels_planes_fn`,
`ops/expand_planes_pallas.py`).

On a v5e chip (jax 0.9) an operation's name in the trace is its HLO
instruction text, and every Pallas (Mosaic) kernel is a custom call
with the target below, whatever the kernel function is called.
"""

PATTERN = r'custom_call_target="tpu_custom_call"'


def read(record):
    trace = record.get("trace")
    if trace is None or trace.busy_ns <= 0:
        return None
    kernel = trace.op_ns(PATTERN) / max(1, trace.devices)
    if kernel <= 0:
        return None
    return 100.0 * kernel / trace.busy_ns
