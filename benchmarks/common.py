"""Shared benchmark plumbing: timing + one-JSON-line-per-result reporting."""

from __future__ import annotations

import json
import time
from typing import Callable


def run_timed(
    name: str,
    fn: Callable[[], object],
    *,
    iters: int = 3,
    warmup: int = 1,
    items: int = 1,
    unit: str = "s",
    label: str = "",
) -> dict:
    """Times `fn` (which must block until done) and prints one JSON line.

    `items` scales the result to a per-item rate (e.g. leaves, points,
    queries); with items > 1 the reported value is items/second.
    """
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    elapsed = (time.perf_counter() - t0) / iters
    result = {
        "benchmark": name,
        "time_s": round(elapsed, 6),
    }
    if items > 1:
        result["items_per_s"] = round(items / elapsed, 2)
        result["ns_per_item"] = round(elapsed / items * 1e9, 3)
    if label:
        result["label"] = label
    print(json.dumps(result), flush=True)
    return result
