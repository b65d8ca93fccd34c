"""Readings behind each `correct` limit: the program on many seeds and
the control on a few, at the cell's own size and load, in one process.

    python3 benchmarks/harness/control.py --workload pir1m.served.poisson \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 5

Each seed builds its own data and requests (as a run of the cell does),
warms up (programs compiled once stay cached in the process), measures
a short window at the cell's traffic and prints one JSON line with the
numbers the check compares. The control is the driver's `control=True`
path (drivers/*.py says what it breaks). The benchmark's own runs never
run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import cell as cell_mod
import run


def readings(entry, seed, seconds, control):
    traffic = cell_mod.traffic(entry["traffic"])
    driver = cell_mod.driver(traffic["driver"]).Driver(
        cell_mod.config(entry["config"]), traffic, seed, run.log,
        control=control,
    )
    try:
        run.prepare(driver)
        window = driver.window(seconds)
        checks = driver.check(window)
    finally:
        driver.close()
    return {
        "seed": seed,
        "control": control,
        "attempted": window["attempted"],
        "checks": {name: value for name, value, _ in checks},
        "limits": {name: limit for name, _, limit in checks},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--cache-dir", default=run.DEFAULT_CACHE)
    args = ap.parse_args()

    entry = cell_mod.workload(cell_mod.benchmark(), args.workload)
    run.use_compile_cache(args.cache_dir)
    run.log(f"device: {run.device_info(int(entry['chips']), True)}")
    for seeds, control in ((args.seeds, False), (args.control_seeds, True)):
        for seed in (int(s) for s in seeds.split(",")):
            line = readings(entry, seed, args.seconds, control)
            print(json.dumps(line), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
