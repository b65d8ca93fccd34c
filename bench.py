"""Headline benchmark: dense PIR queries/sec on one chip at 2^20 x 256 B.

Runs in ONE process on the chip and prints one JSON line
{"metric", "value", "unit", "vs_baseline", "device"} on stdout. No TPU, a
kernel that fails to compile, or a wrong answer raises: the run exits
nonzero and prints no line. A measurement is never replaced by a slower
tier or by an earlier capture.

Baseline: the reference's single-threaded AES-NI CPU path
(`experiments/README.md`, see BASELINE.md). A dense PIR query over 2^20
records costs the reference a full-domain expansion of 2^20 128-bit
selection blocks (~2 fixed-key AES ops per block node, `ExpandSeeds`,
`dpf/distributed_point_function.cc:289-372`) plus a 256MB XOR inner product
(`pir/internal/inner_product_hwy.cc`). From the published 2^20-point
direct-eval time (0.67s, ~20 AES levels/point) the per-AES cost is
~16ns/hash single-threaded; expansion ~2*2^20 hashes ~= 34ms, inner
product ~256MB at ~10GB/s ~= 26ms, about 60ms/query => ~16 queries/sec.
BASELINE_QPS encodes that derived figure.

The measured step is the served single-device pipeline: the serving
expansion (`dense_eval.expansion_impl`) fused with the inner-product tier
the database serves on TPU (`DenseDpfPirDatabase.inner_product_tier`),
one database pass per query batch. Both parties' answers are checked
against the generated records before anything is timed.

Secondary metrics (stderr + benchmarks/results/bench_extra.json): the
inner-product-only time and effective HBM bandwidth, and (BENCH_NSLEAF=1)
the DPF full-domain evaluation ns/leaf at log-domain 20 with uint64
values (`dpf/distributed_point_function_benchmark.cc:43-95`).

Environment knobs: BENCH_RECORDS (default 2^20), BENCH_RECORD_BYTES (256),
BENCH_QUERIES (128), BENCH_ITERS (16, min 1), BENCH_NSLEAF=1 to add the
ns/leaf metric, BENCH_ONLY_NSLEAF=1 to run only it (BENCH_NSLEAF_LD picks
the log-domain), BENCH_XPROF=<dir> for a device trace of a few steps, and
BENCH_HISTORY=0 to skip the history append. BENCH_SERVING=1,
BENCH_HEAVY_HITTERS=1 and BENCH_OVERLOAD=1 run the CPU-scale
`benchmarks/` suites instead and report their own metric.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

BASELINE_QPS = 16.0
# Derived single-thread CPU figure for full-domain eval at 2^20 leaves:
# ~2^21 fixed-key AES ops at ~16 ns plus leaf hashing => ~50 ns/leaf.
BASELINE_NS_PER_LEAF = 50.0


def _log(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _device() -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def _emit(metric, unit, value, vs_baseline, history=True):
    """Print the single JSON result line and append it to the
    trajectory store that `benchmarks/regression_gate.py` reads."""
    line = {
        "metric": metric,
        "value": round(float(value), 2),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 2),
        "status": "ok",
        "device": _device(),
    }
    print(json.dumps(line), flush=True)
    if history and os.environ.get("BENCH_HISTORY", "1") != "0":
        import jax

        from benchmarks.regression_gate import append_record, git_rev

        record = dict(line, git_rev=git_rev(), jax_version=jax.__version__)
        append_record(
            record,
            path=os.environ.get(
                "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
            ),
        )


def _slope_time(fn, iters, reps=3):
    """Min-of-reps slope timing: time(1 call) vs time(1+N calls) with one
    host readback each; the slope isolates device time per call from the
    fixed dispatch+readback cost (execution is in-order). Raises on a
    non-positive slope rather than report a figure it cannot support."""

    def timed(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        np.asarray(out)
        return time.perf_counter() - t0

    t_small = min(timed(1) for _ in range(reps))
    t_big = min(timed(1 + iters) for _ in range(reps))
    if t_big <= t_small:
        raise RuntimeError(
            f"non-positive timing slope (t1={t_small * 1e3:.1f} ms, "
            f"t{1 + iters}={t_big * 1e3:.1f} ms)"
        )
    return (t_big - t_small) / iters, t_small


def _ns_per_leaf(extra):
    """Single-key full-domain eval ns/leaf, uint64 values (reference:
    `distributed_point_function_benchmark.cc:43-95`)."""
    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.value_types import IntType

    log_domain = int(os.environ.get("BENCH_NSLEAF_LD", "20"))
    dpf = DistributedPointFunction.create(
        DpfParameters(log_domain_size=log_domain, value_type=IntType(64))
    )
    key0, _ = dpf.generate_keys(12345 % (1 << log_domain), 42)

    def run():
        return dpf.evaluate_next([], dpf.create_evaluation_context(key0))

    t0 = time.perf_counter()
    np.asarray(run())
    _log(f"ns/leaf: compile+first run {time.perf_counter() - t0:.1f}s "
         f"(log domain {log_domain}, uint64)")
    per_call, _ = _slope_time(run, 4)
    ns = per_call / (1 << log_domain) * 1e9
    metric = f"dpf_full_domain_eval_ns_per_leaf_ld{log_domain}_u64"
    extra[metric] = {
        "value": round(ns, 3),
        "unit": "ns/leaf",
        "vs_baseline_cpu": round(BASELINE_NS_PER_LEAF / ns, 2),
    }
    _log(f"ns/leaf: {ns:.2f} ns/leaf")
    return metric, ns


def _cpu_suite(flag):
    """The CPU-scale suites under benchmarks/: (metric, unit, value,
    vs_baseline), raising when the suite's own oracle check fails."""
    if flag == "BENCH_HEAVY_HITTERS":
        from benchmarks.heavy_hitters_bench import run_heavy_hitters_bench

        r = run_heavy_hitters_bench()
        ok, vals = r["correctness_ok"], (
            "heavy_hitters_sweep_lanes_per_sec", "lanes/s",
            r["best_lanes_per_sec"], r.get("resume_speedup") or 0.0,
        )
    elif flag == "BENCH_SERVING":
        from benchmarks.serving_bench import run_serving_bench

        r = run_serving_bench()
        best, base = r["best_batched_qps"], r["best_unbatched_qps"]
        ok, vals = r["correctness_ok"], (
            "serving_closed_loop_queries_per_sec", "queries/s", best,
            (best / base) if base else 0.0,
        )
    else:
        from benchmarks.overload_bench import run_overload_bench

        r = run_overload_bench()
        ok, vals = r["correctness_ok"], (
            "serving_overload_goodput_queries_per_sec", "queries/s",
            r["overloaded_goodput_qps"], r["goodput_retention"],
        )
    if not ok:
        raise RuntimeError(f"{flag}: responses diverged from the oracle")
    return vals


def main():
    for flag in ("BENCH_HEAVY_HITTERS", "BENCH_SERVING", "BENCH_OVERLOAD"):
        if os.environ.get(flag, "") == "1":
            _emit(*_cpu_suite(flag))
            return

    import jax

    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found {_device()} "
            "(tests run on CPU, the chip path is chip_smoke.py / bench.py "
            "through the chip tool)"
        )
    _log(f"device: {_device()}")

    if os.environ.get("BENCH_ONLY_NSLEAF", "") == "1":
        metric, ns = _ns_per_leaf({})
        _emit(metric, "ns/leaf", ns, BASELINE_NS_PER_LEAF / ns)
        return

    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
    )
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.database import (
        inner_product_tier,
        tier_inner_product,
    )
    from distributed_point_functions_tpu.pir.dense_eval import (
        expansion_impl,
        stage_keys_walked,
    )

    num_records = int(os.environ.get("BENCH_RECORDS", 1 << 20))
    record_bytes = int(os.environ.get("BENCH_RECORD_BYTES", 256))
    num_queries = int(os.environ.get("BENCH_QUERIES", 128))
    iters = max(1, int(os.environ.get("BENCH_ITERS", 16)))
    rng = np.random.default_rng(7)

    num_padded = ((num_records + 127) // 128) * 128
    num_words = record_bytes // 4
    db_host = rng.integers(
        0, 1 << 32, (num_padded, num_words), dtype=np.uint32
    )
    num_blocks = num_padded // 128
    total_levels = max(0, math.ceil(math.log2(num_records)))
    expand_levels = min(max(0, (num_blocks - 1).bit_length()), total_levels)
    walk_levels = total_levels - expand_levels

    tier = inner_product_tier()
    inner_product = tier_inner_product(tier)
    db_dev = jax.device_put(db_host)
    if tier != "jnp":
        db_dev = jax.block_until_ready(permute_db_bitmajor(db_dev))
    expand = expansion_impl()

    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    indices = [int(i) for i in rng.integers(0, num_records, num_queries)]
    keys0, keys1 = client._generate_key_pairs(indices)
    # Serving stages (and host-walks) every fresh key batch, so its host
    # cost is part of the per-batch figure: best of 5 stagings.
    stage_s = []
    for _ in range(5):
        t0 = time.perf_counter()
        staged, device_walk = stage_keys_walked(keys0, walk_levels)
        stage_s.append(time.perf_counter() - t0)
    host_stage_s = min(stage_s)
    staged1, _ = stage_keys_walked(keys1, walk_levels)

    @jax.jit
    def pir_step(s0, c0, cw_s, cw_l, cw_r, vc, db):
        selections = expand(
            s0, c0, cw_s, cw_l, cw_r, vc,
            walk_levels=device_walk,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
        )
        return inner_product(db, selections)

    _log(
        f"compiling: {num_records} records x {record_bytes}B, "
        f"{num_queries} queries, expand={expand_levels} "
        f"(+{walk_levels - device_walk} host walk levels), "
        f"expansion {expand.__name__}, inner product {tier}"
    )
    t0 = time.perf_counter()
    out0 = np.asarray(pir_step(*staged, db_dev))
    _log(f"compile+first run {time.perf_counter() - t0:.1f}s")
    out1 = np.asarray(pir_step(*staged1, db_dev))
    if not np.array_equal(out0 ^ out1, db_host[np.asarray(indices)]):
        raise RuntimeError("shares do not reconstruct the queried records")
    _log(f"share check: {num_queries} queries reconstructed exactly")

    xprof_dir = os.environ.get("BENCH_XPROF", "")
    if xprof_dir:
        from distributed_point_functions_tpu.utils.profiling import trace

        with trace(xprof_dir):
            for _ in range(3):
                np.asarray(pir_step(*staged, db_dev))
        _log(f"xprof trace captured to {xprof_dir}")

    per_batch, latency = _slope_time(
        lambda: pir_step(*staged, db_dev), iters
    )
    expand_only = jax.jit(
        lambda s0, c0, cs, cl, cr, vc: expand(
            s0, c0, cs, cl, cr, vc,
            walk_levels=device_walk,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
        )
    )
    sel = jax.block_until_ready(expand_only(*staged))
    per_ip, _ = _slope_time(lambda: inner_product(db_dev, sel), iters)
    db_gb = num_padded * num_words * 4 / 1e9
    qps = num_queries / (per_batch + host_stage_s)
    _log(
        f"per-batch {per_batch * 1e3:.3f} ms (latency {latency * 1e3:.1f} "
        f"ms), inner product alone {per_ip * 1e3:.3f} ms "
        f"({db_gb / per_ip:.1f} GB/s), host key staging "
        f"{host_stage_s * 1e3:.3f} ms"
    )
    extra = {
        "device": _device(),
        "per_batch_ms": round(per_batch * 1e3, 3),
        "inner_product_only_ms": round(per_ip * 1e3, 3),
        "inner_product_effective_gbps": round(db_gb / per_ip, 2),
        "inner_product_path": tier,
        "expansion_path": expand.__name__,
        "host_key_staging_ms": round(host_stage_s * 1e3, 3),
        "num_queries": num_queries,
    }
    if os.environ.get("BENCH_NSLEAF", "") == "1":
        _ns_per_leaf(extra)
    os.makedirs("benchmarks/results", exist_ok=True)
    with open("benchmarks/results/bench_extra.json", "w") as f:
        json.dump(extra, f, indent=2)
    _emit(
        f"dense_pir_queries_per_sec_chip_{num_records}x{record_bytes}B",
        "queries/s", qps, qps / BASELINE_QPS,
    )


if __name__ == "__main__":
    main()
