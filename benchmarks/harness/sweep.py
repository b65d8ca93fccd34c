"""Find the highest rate a served cell sustains: one set-up, then one
window per offered rate, in one process on the chip.

    python3 benchmarks/harness/sweep.py --workload pir1m.served.poisson \
        --seed 11 --seconds 10 --rates 40,80,120,160,200,260,320

For each rate it prints one JSON line: offered and answered queries/s,
p50/p95/p99 latency, and p95 of the first and the last third of the
window's arrivals (a backlog that grows shows as a last third far above
the first). The rate a cell runs at is fixed in its traffic file from
this; the benchmark's own runs never search.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
import cell as cell_mod


def summary(rate, window):
    lat = np.asarray(window["latencies_ms"])
    third = max(1, len(lat) // 3)
    return {
        "offered_qps": rate,
        "answered_qps": len(lat) / window["drain_s"],
        "attempted": window["attempted"],
        "failed": window["failed"],
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p99_ms": float(np.percentile(lat, 99)),
        "p95_first_third_ms": float(np.percentile(lat[:third], 95)),
        "p95_last_third_ms": float(np.percentile(lat[-third:], 95)),
        "batch_keys_mean": (
            window["batchers"]["leader"]["requests_submitted"]
            / max(1, window["batchers"]["leader"]["batches"])
        ),
        "late_max_ms": max(window["late_ms"] or [0.0]),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--cache-dir", default=run.DEFAULT_CACHE)
    args = ap.parse_args()

    bench = cell_mod.benchmark()
    entry = cell_mod.workload(bench, args.workload)
    run.use_compile_cache(args.cache_dir)
    run.log(f"device: {run.device_info(int(entry['chips']), True)}")
    traffic = cell_mod.traffic(entry["traffic"])
    driver = cell_mod.driver(traffic["driver"]).Driver(
        cell_mod.config(entry["config"]), traffic, args.seed, run.log
    )
    run.prepare(driver)
    run.log(f"set-up {run.time.perf_counter() - run.T_START:.1f} s")
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic["arrivals"]["rate_qps"] = rate
            window = driver.window(args.seconds)
            checks = driver.check(window)
            line = dict(summary(rate, window), checks={
                name: value for name, value, _ in checks
            })
            print(json.dumps(line), flush=True)
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
