"""Leaves that left an expansion through the per-leaf gather exit
(`dpf._gather_exit`) per resumed level: the deltas of the program's
`dpf.exit.gather_leaves` and `dpf.hier.evaluations` counters over the
traced part of the window. Layer: expansion. Program counters."""


def read(record):
    counters = (record.get("traced") or {}).get("counters") or {}
    evaluations = counters.get("dpf.hier.evaluations")
    if not evaluations:
        return None
    return counters.get("dpf.exit.gather_leaves", 0) / evaluations
