"""95th percentile latency over every request scheduled in the window,
from its scheduled send time to its answer (open loop), or from its send
(closed loop). Requests still in flight when the window closes are
awaited and counted. Host clock."""

import numpy as np


def read(record):
    latencies = record["window"].get("latencies_ms")
    if not latencies:
        return None
    return float(np.percentile(latencies, 95))
