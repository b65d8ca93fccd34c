"""Seeded arrival schedules for open-loop traffic.

Every seed gets the same number of arrivals: a Poisson process
conditioned on its count places that many arrivals uniformly at random
in the window (sorted). So seeds change where requests fall, never how
much work a run offers, and a rate metric does not swing with the
Poisson count. The arithmetic follows `benchmarks/workload_gen.py`'s
`arrival_times` (exponential gaps at a fixed rate plus back-to-back
bursts), with the count fixed.

    poisson  {"kind": "poisson", "rate_qps": R}
    bursty   {"kind": "bursty", "rate_qps": R, "burst_size": B,
              "burst_interval_s": T}   (B extra arrivals every T seconds,
              the first at a seeded phase inside the first interval)
    closed   {"kind": "closed", "clients": C}  (no schedule: each client
              sends its next request when the last one is answered)
"""

from __future__ import annotations

from typing import List, Optional


def schedule(arrivals: dict, seconds: float, rng) -> Optional[List[float]]:
    """Sorted send offsets in [0, seconds), or None for a closed loop."""
    kind = arrivals["kind"]
    if kind == "closed":
        return None
    if kind not in ("poisson", "bursty"):
        raise ValueError(f"unknown arrival kind {kind!r}")
    count = int(round(float(arrivals["rate_qps"]) * seconds))
    times = list(rng.uniform(0.0, seconds, count))
    if kind == "bursty":
        size = int(arrivals["burst_size"])
        interval = float(arrivals["burst_interval_s"])
        t = float(rng.uniform(0.0, interval))
        while t < seconds:
            times.extend([t] * size)
            t += interval
    return sorted(float(t) for t in times)


def offered_rate(arrivals: dict) -> Optional[float]:
    """Mean offered queries/s of an open-loop mix (None when closed)."""
    if arrivals["kind"] == "poisson":
        return float(arrivals["rate_qps"])
    if arrivals["kind"] == "bursty":
        return float(arrivals["rate_qps"]) + (
            float(arrivals["burst_size"]) / float(arrivals["burst_interval_s"])
        )
    return None
