"""Bytes the hierarchical path copied from the device to the host per
resumed level: the deltas of the program's `dpf.hier.readback_bytes`
and `dpf.hier.evaluations` counters over the traced part of the window
(0 while the frontier stays on the device). Layer: frontier state.
Program counters."""


def read(record):
    counters = (record.get("traced") or {}).get("counters") or {}
    evaluations = counters.get("dpf.hier.evaluations")
    if not evaluations:
        return None
    return counters.get("dpf.hier.readback_bytes", 0) / evaluations
