"""Heavy-hitters sweep benchmark: per-level latency, prune ratio, reuse.

Drives the full two-server sweep (`heavy_hitters.session` over an
in-process transport) across a `clients x domain-bits x threshold`
grid, and measures the tentpole claim directly: each grid point also
runs a *from-root* sweep — identical rounds, but the aggregator's
cut-state cache is dropped before every level so evaluation re-expands
from the root — giving the cut-state-reuse speedup as the report's
`vs_baseline` analog.

Every point's private answer is checked against the plaintext oracle,
so the throughput claim carries an equal-correctness proof in the same
run, exactly like `serving_bench`. Metric definitions:

* **lane** — one (key, prefix) evaluation inside a fused level batch;
  `lanes_per_sec` is total lanes over the measured sweep wall clock,
  the sweep's q/s-equivalent.
* **prune_ratio** — per round, the fraction of the candidate frontier
  the threshold killed.
* **cut-state hit rate** — prefixes served from cached cuts over total
  prefixes evaluated (from the `hh.cut_resume_prefixes` /
  `hh.root_eval_prefixes` counters).

Run directly (one JSON report on stdout, also written to
``benchmarks/results/heavy_hitters_bench.json``)::

    JAX_PLATFORMS=cpu python -m benchmarks.heavy_hitters_bench

or through the headline harness (one bench-style JSON line)::

    JAX_PLATFORMS=cpu BENCH_HEAVY_HITTERS=1 python bench.py

Environment knobs: HH_BENCH_CLIENTS (default 48), HH_BENCH_DOMAIN_BITS
("16"), HH_BENCH_LEVEL_BITS (4), HH_BENCH_THRESHOLDS ("2,4"),
HH_BENCH_OUT (report path; empty string disables the file),
BENCH_HISTORY ("0" skips the history.jsonl residual append).

The report also carries `cost_model_residual_p50` for the "hh"
workload — the cost ledger's samples-weighted |residual_p50| over the
measured sweeps' folded levels, appended to history.jsonl with
direction "lower" (report-only; same shape as serving_bench's pir
aggregate).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time


def _log(msg: str) -> None:
    print(f"[hh-bench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _skewed_values(num_clients: int, domain_bits: int, seed: int):
    """Zipf-ish population: a few hot values, a long random tail."""
    rng = random.Random(seed)
    hot = [rng.randrange(1 << domain_bits) for _ in range(4)]
    weights = [num_clients // 4, num_clients // 6, num_clients // 8,
               num_clients // 10]
    values = []
    for v, w in zip(hot, weights):
        values.extend([v] * max(1, w))
    while len(values) < num_clients:
        values.append(rng.randrange(1 << domain_bits))
    rng.shuffle(values)
    return values[:num_clients]


def _sweep_leader_helper(config, keys0, keys1, metrics):
    """One full Leader/Helper sweep over an in-process transport;
    returns (result, wall_s)."""
    from distributed_point_functions_tpu import heavy_hitters as hh
    from distributed_point_functions_tpu.serving.transport import (
        InProcessTransport,
    )

    s0 = hh.HeavyHittersServer(config, keys0, metrics=metrics)
    s1 = hh.HeavyHittersServer(config, keys1, metrics=metrics)
    leader = hh.HeavyHittersLeader(
        s0, InProcessTransport(hh.HeavyHittersHelper(s1).handle_wire),
        metrics=metrics,
    )
    t0 = time.perf_counter()
    result = leader.run()
    return result, time.perf_counter() - t0


def _sweep_from_root(config, keys0, keys1):
    """The same rounds with the cut-state cache dropped before every
    level — the re-expand-from-root baseline; returns (result, wall_s)."""
    from distributed_point_functions_tpu import heavy_hitters as hh

    s0 = hh.HeavyHittersServer(config, keys0)
    s1 = hh.HeavyHittersServer(config, keys1)
    sweep = hh.FrontierSweep(config)
    t0 = time.perf_counter()
    while not sweep.done:
        r, frontier = sweep.round_index, sweep.frontier
        s0.aggregator.reset()
        s1.aggregator.reset()
        counts = hh.reconstruct_counts(
            s0.aggregator.evaluate_level(r, frontier),
            s1.aggregator.evaluate_level(r, frontier),
            config.count_bits,
        )
        sweep.observe_counts(counts)
    wall = time.perf_counter() - t0
    return (
        hh.HeavyHittersResult(
            heavy_hitters=sweep.result, rounds=sweep.rounds
        ),
        wall,
    )


def run_heavy_hitters_bench():
    """Sweep the grid, check each point against the oracle, return the
    report dict (also written to HH_BENCH_OUT unless empty)."""
    from distributed_point_functions_tpu import heavy_hitters as hh
    from distributed_point_functions_tpu.observability import tracing
    from distributed_point_functions_tpu.serving.metrics import (
        MetricsRegistry,
    )

    tracing.reset_stages()

    num_clients = int(os.environ.get("HH_BENCH_CLIENTS", 48))
    level_bits = int(os.environ.get("HH_BENCH_LEVEL_BITS", 4))
    domain_bits_list = [
        int(b)
        for b in os.environ.get("HH_BENCH_DOMAIN_BITS", "16").split(",")
        if b.strip()
    ]
    thresholds = [
        int(t)
        for t in os.environ.get("HH_BENCH_THRESHOLDS", "2,4").split(",")
        if t.strip()
    ]

    metrics = MetricsRegistry()
    points = []
    correctness_ok = True
    for domain_bits in domain_bits_list:
        for threshold in thresholds:
            config = hh.HeavyHittersConfig(
                domain_bits=domain_bits,
                level_bits=level_bits,
                threshold=threshold,
            )
            values = _skewed_values(num_clients, domain_bits, seed=13)
            client = hh.HeavyHittersClient(config)
            pairs = [client.generate_report(v) for v in values]
            keys0 = [p[0] for p in pairs]
            keys1 = [p[1] for p in pairs]

            # Warm run compiles every jit shape bucket the sweep needs;
            # the measured run then reflects steady-state level latency.
            _sweep_leader_helper(config, keys0, keys1, MetricsRegistry())
            metrics.reset()
            result, wall_s = _sweep_leader_helper(
                config, keys0, keys1, metrics
            )
            snap = metrics.snapshot()

            want = hh.plaintext_heavy_hitters(values, config)
            ok = result.as_dict() == want
            correctness_ok = correctness_ok and ok

            # Warm the from-root shapes too (each level's full-depth
            # walk is a distinct program) so the speedup compares
            # steady-state sweeps, not resume vs cold compiles.
            _sweep_from_root(config, keys0, keys1)
            root_result, root_wall_s = _sweep_from_root(
                config, keys0, keys1
            )
            ok_root = root_result.as_dict() == want
            correctness_ok = correctness_ok and ok_root

            lanes = sum(
                st.frontier_width * num_clients for st in result.rounds
            )
            resume = snap["counters"].get("hh.cut_resume_prefixes", 0)
            root = snap["counters"].get("hh.root_eval_prefixes", 0)
            point = {
                "num_clients": num_clients,
                "domain_bits": domain_bits,
                "level_bits": level_bits,
                "threshold": threshold,
                "num_rounds": len(result.rounds),
                "heavy_hitters": len(result.heavy_hitters),
                "sweep_wall_s": round(wall_s, 4),
                "from_root_wall_s": round(root_wall_s, 4),
                "resume_speedup": round(root_wall_s / wall_s, 2)
                if wall_s
                else None,
                "lanes": lanes,
                "lanes_per_sec": round(lanes / wall_s, 1) if wall_s else 0.0,
                "cut_state_hit_rate": round(
                    resume / (resume + root), 4
                ) if (resume + root) else 0.0,
                "rounds": [
                    {
                        "round": st.round_index,
                        "bit_width": st.bit_width,
                        "frontier_width": st.frontier_width,
                        "survivors": st.survivors,
                        "prune_ratio": round(st.prune_ratio, 4),
                        "wall_ms": round(st.wall_ms, 2),
                        "bytes_on_wire": st.bytes_sent + st.bytes_received,
                    }
                    for st in result.rounds
                ],
                "correctness_ok": ok and ok_root,
            }
            points.append(point)
            _log(
                f"d={domain_bits} t={threshold}: "
                f"{point['lanes_per_sec']:.0f} lanes/s over "
                f"{point['num_rounds']} rounds, resume speedup "
                f"{point['resume_speedup']}x, hit rate "
                f"{point['cut_state_hit_rate']}, "
                f"correct={'ok' if point['correctness_ok'] else 'FAILED'}"
            )

    # Cost-model accuracy: every folded level in the measured sweeps
    # joined its admission-time frontier price against the measured
    # fold in the default cost ledger. Same aggregate (samples-weighted
    # mean |residual_p50|) and history metric shape as serving_bench's
    # pir workload — report-only, direction "lower".
    from benchmarks.serving_bench import workload_residual_summary
    from distributed_point_functions_tpu.observability import (
        costmodel as costmodel_mod,
    )

    cost_model_residual = workload_residual_summary(
        costmodel_mod.default_cost_ledger().export(), "hh"
    )
    if cost_model_residual["cells"]:
        _log(
            f"cost-model residual (hh): "
            f"|p50| {cost_model_residual['residual_p50_abs']:.3f} over "
            f"{cost_model_residual['samples']} folded levels in "
            f"{len(cost_model_residual['cells'])} cells"
        )

    best = max(p["lanes_per_sec"] for p in points)
    speedups = [p["resume_speedup"] for p in points if p["resume_speedup"]]
    report = {
        "config": {
            "num_clients": num_clients,
            "level_bits": level_bits,
            "domain_bits": domain_bits_list,
            "thresholds": thresholds,
        },
        "sweep": points,
        "best_lanes_per_sec": best,
        "resume_speedup": round(sum(speedups) / len(speedups), 2)
        if speedups
        else None,
        "correctness_ok": correctness_ok,
        "cost_model_residual_p50": cost_model_residual,
        # Sweep-wide span summary (helper_evaluate / leader_own_share /
        # reconstruct / round percentiles) and the final measured
        # point's metrics snapshot.
        "stage_spans": tracing.stage_summary(),
        "metrics_snapshot": snap,
    }

    out = os.environ.get(
        "HH_BENCH_OUT", "benchmarks/results/heavy_hitters_bench.json"
    )
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        _log(f"report written to {out}")
    return report


def main():
    report = run_heavy_hitters_bench()
    print(json.dumps(report, indent=2))
    if os.environ.get("BENCH_HISTORY", "1") != "0":
        from benchmarks.serving_bench import append_residual_history

        append_residual_history(
            report["cost_model_residual_p50"], bench="heavy_hitters_bench"
        )
    if not report["correctness_ok"]:
        raise SystemExit("heavy-hitters bench FAILED correctness")


if __name__ == "__main__":
    main()
