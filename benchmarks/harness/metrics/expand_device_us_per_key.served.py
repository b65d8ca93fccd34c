"""Device microseconds per evaluated key of the ops under the program
scope `pir.expand` (the selection-block expansion programs,
`pir/dense_eval.py`, `pir/dense_eval_planes.py`), read from each op's
scope in the raw trace (xplane_scopes.py) over the traced window, over
the keys both parties' batchers evaluated in it (requests plus bucket
padding). Unlike `expand_us_per_key.served` it leaves out the layout
glue outside the expansion programs. Layer: expansion. Device trace."""

import spanlog


def read(record):
    scopes, window = spanlog.scopes(record), spanlog.window(record)
    batchers = (record.get("traced") or {}).get("counters")
    if scopes is None or window is None or not batchers:
        return None
    keys = sum(
        party["requests_submitted"] + party["padded_keys"]
        for party in batchers.values()
    )
    ns = scopes.scope_ns("pir.expand", window)
    if not keys or ns <= 0:
        return None
    return ns / 1e3 / keys
