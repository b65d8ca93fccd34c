"""Share of its roofline that the XOR inner product reaches on the
served path, both parties together.

Work from shapes (work.py `inner_product_bytes`): for every batch the
two parties' batchers formed while the trace ran, by its padded bucket,
the whole table once, the selection bits and the answers; independent
of which tier runs it. The least time is that work over the chip's HBM
bandwidth (peaks.json); the inner product is bound by bytes, its bit
operations run far below the int8 peak. The time is the device time of
every operation in the inner-product program, found by its program
(XLA module) name below: the Pallas kernel, the parity fold after it,
and the relayout copy of the staged table that the program makes on
every call (on a v5e chip in 2026-10 the largest single operation of
the served path).
Layer: XOR inner product (`ops/inner_product_pallas.py`, pallas2).
"""

import work

# The jitted pallas2 entry, `_ip_pallas_staged_v2` (XLA module
# `jit__ip_pallas_staged_v2`), holding the `_ip_kernel_v2` kernel.
MODULE = r"ip_pallas_staged"


def read(record):
    trace = record.get("trace")
    batchers = (record.get("traced") or {}).get("counters")
    if trace is None or not batchers:
        return None
    seconds = trace.op_ns(MODULE, field="module") / 1e9
    if seconds <= 0:
        return None
    shapes = record["window"]["shapes"]
    moved = sum(
        int(count) * work.inner_product_bytes(
            int(bucket), shapes["num_records"], shapes["record_bytes"]
        )
        for party in batchers.values()
        for bucket, count in party["bucket_batches"].items()
        if bucket != "+inf" and count
    )
    least = moved / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
