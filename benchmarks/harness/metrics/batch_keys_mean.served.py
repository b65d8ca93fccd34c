"""Mean keys per batch the Leader's batcher formed: the deltas of its
`leader.batcher.requests_submitted` and `leader.batcher.batches`
counters (one key per request), over the traced part of the window in a
traced run. Layer: batcher. Program counters."""


def read(record):
    traced = record.get("traced") or {}
    batchers = traced.get("counters") or record["window"].get("batchers", {})
    leader = batchers.get("leader")
    if not leader or not leader["batches"]:
        return None
    return leader["requests_submitted"] / leader["batches"]
