"""Closed-loop serving benchmark: dynamic batcher vs serialized requests.

Drives a `serving.PlainSession` with a closed-loop offered-load sweep —
`c` client threads, each issuing its next request the moment the previous
one returns — at several concurrency levels, in two modes:

* **batched**: the session's `DynamicBatcher` coalesces concurrent
  requests into padded power-of-two key batches (the serving/ tentpole);
* **unbatched**: the same session class with `batching=False`, so every
  request pays its own `handle_plain_request` device step — the
  one-request-at-a-time baseline.

The batched sweep also reads the cost-model accuracy ledger
(`observability/costmodel.py`) the session populated while serving: a
report-only `cost_model_residual_p50` per workload (abs signed-ratio
error of the capacity model's device-ms price, lower is better) that
`main()` appends to `benchmarks/results/history.jsonl`, and a
`ledger_overhead` point measuring the q/s cost of the per-batch
predicted-vs-actual join against a short-circuited ledger (same <2%
review budget as the prober and digest points).

Every response is compared bit-for-bit against an oracle computed
upfront by a direct (no serving runtime) `DenseDpfPirServer`, so the
throughput claim carries an equal-correctness proof in the same run.
The report includes the batched session's full metrics export — batch
size histogram, padding waste, and the jit bucket compile/hit counters
that demonstrate the bounded-compilation property — plus two
report-only overhead points: `prober_overhead` measures the q/s cost
of running the blackbox verification prober (`serving/prober.py`)
alongside real traffic, and `digest_overhead` measures the q/s cost of
the v2 envelope's critical-path digest piggyback (Helper phase
waterfall + recv/send timestamps on every reply; pinned off via
`ServingConfig(helper_digest=False)`) on the encrypted Leader->Helper
path. Both ride a <2% budget reviewed from the report, not gated in
CI.

A **pipeline A/B leg** runs the same batched point at pipeline depth 1
(the serial pre-pipeline worker) and depth 2 (async dispatch with a
completion thread, the default), emitting the gated
`serving_qps_pipelined` history record (direction "higher") plus two
staging-side companions — `pipelined_staging_hidden_ms` (overlapped
H2D time a pipelined full staging hid behind host work) and
`rotation_prestage_bytes_saved` (bytes a ~1%-row delta rotation's
prestage kept off the bus) — and a report-only `pipeline_overhead`
percentage under the same <2% budget.

Run directly (one JSON report on stdout, also written to
``benchmarks/results/serving_bench.json``)::

    JAX_PLATFORMS=cpu python -m benchmarks.serving_bench

or through the headline harness (one bench-style JSON line)::

    JAX_PLATFORMS=cpu BENCH_SERVING=1 python bench.py

The bench closes with a **mesh stage**: the same closed-loop point
served by one logical server spread over a 2-D device mesh
(database-shard axis x key-batch axis, `parallel.ShardedServingPlan`),
bit-checked against the same oracle. It emits a
`serving_qps_{ndev}dev` history record (direction "higher") plus the
donation accounting — TransferLedger `selection_scratch` copies before
and after the timed loop, proving the donated scratch stages once, not
per request. When invoked directly on a single-device CPU host, the
bench forces `--xla_force_host_platform_device_count=8` before JAX
initializes so a record always lands; the forced-host CPU numbers gate
correctness and relayout accounting only, not throughput.

A **sparse ladder** closes the report: closed-loop string-keyed
(cuckoo key-value) traffic through a `SparsePlainSession` with the
batcher on, every masked response bit-checked against an unbatched
sparse oracle, then a ~1%-key write batch landed as a SnapshotManager
delta rotation on the live session. It emits two gated history records
— `sparse_qps` (direction "higher") and
`sparse_rotation_prestage_bytes_saved` (direction "higher", the bytes
the touched-row prestage kept off the bus).

Environment knobs: SERVING_BENCH_RECORDS (default 2048),
SERVING_BENCH_RECORD_BYTES (32), SERVING_BENCH_CONCURRENCY ("1,4,16"),
SERVING_BENCH_REQUESTS (total closed-loop requests per sweep point,
default 64), SERVING_BENCH_MAX_BATCH (16), SERVING_BENCH_PROBER_PERIOD_S
(cadence for the overhead point, default 5.0 — the prober default),
SERVING_BENCH_MESH ("0" skips the mesh stage),
SERVING_BENCH_SPARSE ("0" skips the sparse ladder),
SERVING_BENCH_SPARSE_KEYS (sparse ladder key count, default 512),
SERVING_BENCH_OUT (report path; empty string disables the file),
BENCH_HISTORY ("0" skips the history.jsonl residual append),
BENCH_HISTORY_PATH (append target, default
benchmarks/results/history.jsonl).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def force_host_devices(count: int = 8) -> None:
    """CPU fallback for the mesh stage: force `count` virtual host
    devices so a `serving_qps_{ndev}dev` record always lands, even on a
    1-CPU box. Only effective before JAX initializes (XLA reads the
    flag at backend creation), so a no-op when jax is already imported
    or a device count is already forced."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={count}".strip()
    )


def _log(msg: str) -> None:
    print(f"[serving-bench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


def workload_residual_summary(ledger_export, workload):
    """Collapse one workload's cost-ledger cells into a single
    report-line number: the samples-weighted mean of per-cell signed
    `residual_p50` and of its absolute value (the history metric —
    0 means the capacity model priced the work exactly).

    Shared with `heavy_hitters_bench` so both workloads report the
    same aggregate.
    """
    prefix = f"{workload}/"
    cells = {
        name: cell
        for name, cell in ledger_export.get("cells", {}).items()
        if name.startswith(prefix) and cell.get("residual_p50") is not None
    }
    total = sum(c["samples"] for c in cells.values())
    if not total:
        return {"workload": workload, "samples": 0, "cells": {},
                "residual_p50": None, "residual_p50_abs": None}
    signed = sum(
        c["residual_p50"] * c["samples"] for c in cells.values()
    ) / total
    absolute = sum(
        abs(c["residual_p50"]) * c["samples"] for c in cells.values()
    ) / total
    return {
        "workload": workload,
        "samples": total,
        "residual_p50": round(signed, 4),
        "residual_p50_abs": round(absolute, 4),
        "cells": {
            name: {
                "samples": c["samples"],
                "residual_p50": c["residual_p50"],
            }
            for name, c in cells.items()
        },
    }


def append_residual_history(summary, bench):
    """Best-effort: append the |residual_p50| aggregate for one
    workload to `benchmarks/results/history.jsonl` as metric
    `cost_model_residual_p50_<workload>` with explicit
    ``direction: "lower"`` — report-only in spirit (the regression
    gate needs 2 clean priors before it judges, and the record is
    plainly labeled), never fatal to the bench."""
    if summary["samples"] == 0 or summary["residual_p50_abs"] is None:
        return
    try:
        from benchmarks.regression_gate import append_record, git_rev

        append_record(
            {
                "metric": f"cost_model_residual_p50_{summary['workload']}",
                "value": float(summary["residual_p50_abs"]),
                "unit": "abs_ratio_error",
                "direction": "lower",
                "status": "ok",
                "vs_baseline": None,
                "git_rev": git_rev(),
                "device": os.environ.get("BENCH_PLATFORM", "cpu"),
                "bench": bench,
                "samples": summary["samples"],
            },
            path=os.environ.get(
                "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
            ),
        )
    except Exception as e:  # noqa: BLE001 - accounting never fails a bench
        _log(f"history append skipped: {e}")


def append_mesh_history(mesh_point, bench):
    """Best-effort: record the mesh-stage throughput as
    `serving_qps_{ndev}dev` (direction "higher" — the whole point of
    sharding is that this number scales with the device count) plus a
    `serving_mesh_donation_saved_copies` companion documenting the
    buffer-donation win (scratch copies the donated entry point did
    NOT re-stage, one per batch when donation works). Status is "ok"
    only when the mesh actually served (no tier-demotion fallback) and
    every response matched the single-device oracle."""
    if not mesh_point:
        return
    try:
        from benchmarks.regression_gate import append_record, git_rev

        path = os.environ.get(
            "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
        )
        ok = mesh_point["mesh_served"] and mesh_point["mismatches"] == 0
        rev = git_rev()
        device = os.environ.get("BENCH_PLATFORM", "cpu")
        append_record(
            {
                "metric": f"serving_qps_{mesh_point['devices']}dev",
                "value": float(mesh_point["qps"]),
                "unit": "queries/s",
                "direction": "higher",
                "status": "ok" if ok else "mesh_fallback",
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "mesh_shape": mesh_point["mesh_shape"],
                "concurrency": mesh_point["concurrency"],
            },
            path=path,
        )
        append_record(
            {
                "metric": "serving_mesh_donation_saved_copies",
                "value": float(mesh_point["donation_saved_copies"]),
                "unit": "h2d_copies",
                "direction": "higher",
                "status": "ok" if ok else "mesh_fallback",
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "scratch_copies_before": mesh_point["scratch_copies_before"],
                "scratch_copies_after": mesh_point["scratch_copies_after"],
                "batches": mesh_point["batches"],
            },
            path=path,
        )
    except Exception as e:  # noqa: BLE001 - accounting never fails a bench
        _log(f"mesh history append skipped: {e}")


def append_pipeline_history(point, bench):
    """Best-effort: append the three hot-path-pipelining records the
    regression gate locks in — `serving_qps_pipelined` (the depth-2
    closed-loop throughput), `pipelined_staging_hidden_ms` (overlapped
    H2D milliseconds a pipelined full staging hid behind host work),
    and `rotation_prestage_bytes_saved` (bytes a ~1%-row delta
    rotation's prestage kept off the bus) — all direction "higher".
    The depth-1-vs-2 `pipeline_overhead` percentage stays report-only
    (<2% budget reviewed from the report, not gated). Never fatal to
    the bench."""
    if not point:
        return
    try:
        from benchmarks.regression_gate import append_record, git_rev

        path = os.environ.get(
            "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
        )
        rev = git_rev()
        device = os.environ.get("BENCH_PLATFORM", "cpu")
        status = "ok" if point["mismatches"] == 0 else "mismatch"
        append_record(
            {
                "metric": "serving_qps_pipelined",
                "value": float(point["pipelined_qps"]),
                "unit": "queries/s",
                "direction": "higher",
                "status": status,
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "concurrency": point["concurrency"],
                "serial_qps": point["serial_qps"],
                "overhead_pct": point["overhead_pct"],
            },
            path=path,
        )
        append_record(
            {
                "metric": "pipelined_staging_hidden_ms",
                "value": float(point["staging_hidden_ms"]),
                "unit": "ms",
                "direction": "higher",
                "status": status,
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
            },
            path=path,
        )
        append_record(
            {
                "metric": "rotation_prestage_bytes_saved",
                "value": float(point["prestage_bytes_saved"]),
                "unit": "bytes",
                "direction": "higher",
                "status": status,
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "rows_touched": point["prestage_rows_touched"],
                "bytes_full_image": point["prestage_bytes_full_image"],
                "prestage_mode": point["prestage_mode"],
            },
            path=path,
        )
    except Exception as e:  # noqa: BLE001 - accounting never fails a bench
        _log(f"pipeline history append skipped: {e}")


def append_utilization_history(point, bench):
    """Best-effort: append the two device-seconds-ledger records the
    regression gate locks in — `device_duty_cycle_pct` (the fraction of
    tracked worker time spent feeding the device, direction "higher")
    and `pipeline_bubble_ms_p99` (the tail of the typed idle-bubble
    reservoir, direction "lower"). The off-vs-on `overhead_pct` stays
    report-only (<2% budget reviewed from the report, not gated).
    Never fatal to the bench."""
    if not point or point.get("duty_cycle_pct") is None:
        return
    try:
        from benchmarks.regression_gate import append_record, git_rev

        path = os.environ.get(
            "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
        )
        rev = git_rev()
        device = os.environ.get("BENCH_PLATFORM", "cpu")
        status = "ok" if point["mismatches"] == 0 else "mismatch"
        append_record(
            {
                "metric": "device_duty_cycle_pct",
                "value": float(point["duty_cycle_pct"]),
                "unit": "pct",
                "direction": "higher",
                "status": status,
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "concurrency": point["concurrency"],
                "overhead_pct": point["overhead_pct"],
            },
            path=path,
        )
        if point.get("bubble_ms_p99") is not None:
            append_record(
                {
                    "metric": "pipeline_bubble_ms_p99",
                    "value": float(point["bubble_ms_p99"]),
                    "unit": "ms",
                    "direction": "lower",
                    "status": status,
                    "vs_baseline": None,
                    "git_rev": rev,
                    "device": device,
                    "bench": bench,
                    "bubbles": point["bubbles"],
                    "bubble_causes": point["bubble_causes"],
                },
                path=path,
            )
    except Exception as e:  # noqa: BLE001 - accounting never fails a bench
        _log(f"utilization history append skipped: {e}")


def append_sparse_history(point, bench):
    """Best-effort: append the two sparse-serving records the
    regression gate locks in — `sparse_qps` (closed-loop key-value
    throughput through the batched session, direction "higher") and
    `sparse_rotation_prestage_bytes_saved` (bytes a ~1%-key write
    batch's delta prestage kept off the bus, direction "higher").
    Never fatal to the bench."""
    if not point:
        return
    try:
        from benchmarks.regression_gate import append_record, git_rev

        path = os.environ.get(
            "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
        )
        rev = git_rev()
        device = os.environ.get("BENCH_PLATFORM", "cpu")
        status = "ok" if point["mismatches"] == 0 else "mismatch"
        append_record(
            {
                "metric": "sparse_qps",
                "value": float(point["qps"]),
                "unit": "queries/s",
                "direction": "higher",
                "status": status,
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "num_keys": point["num_keys"],
                "num_buckets": point["num_buckets"],
                "concurrency": point["concurrency"],
            },
            path=path,
        )
        append_record(
            {
                "metric": "sparse_rotation_prestage_bytes_saved",
                "value": float(point["prestage_bytes_saved"]),
                "unit": "bytes",
                "direction": "higher",
                "status": status,
                "vs_baseline": None,
                "git_rev": rev,
                "device": device,
                "bench": bench,
                "keys_touched": point["rotation_keys_touched"],
                "bytes_full_image": point["prestage_bytes_full_image"],
                "prestage_mode": point["prestage_mode"],
            },
            path=path,
        )
    except Exception as e:  # noqa: BLE001 - accounting never fails a bench
        _log(f"sparse history append skipped: {e}")


def _closed_loop(handle, requests, concurrency):
    """Run `requests` through `handle` from `concurrency` closed-loop
    client threads; returns (wall_seconds, latencies_ms, responses)."""
    next_idx = [0]
    lock = threading.Lock()
    latencies = [0.0] * len(requests)
    responses = [None] * len(requests)
    errors = []

    def client():
        while True:
            with lock:
                i = next_idx[0]
                if i >= len(requests):
                    return
                next_idx[0] = i + 1
            t0 = time.perf_counter()
            try:
                responses[i] = handle(requests[i])
            except Exception as e:  # noqa: BLE001 - collected, not raised
                with lock:
                    errors.append(f"request {i}: {e}")
                return
            latencies[i] = (time.perf_counter() - t0) * 1e3
    threads = [
        threading.Thread(target=client, name=f"closed-loop-{t}")
        for t in range(concurrency)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError("; ".join(errors[:3]))
    return wall, latencies, responses


def run_serving_bench():
    """Build the database, sweep (mode x concurrency), return the report
    dict (also written to SERVING_BENCH_OUT unless empty)."""
    from distributed_point_functions_tpu.pir import messages
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )
    from distributed_point_functions_tpu.observability import tracing
    from distributed_point_functions_tpu.pir.server import (
        DenseDpfPirServer,
        set_tier_floor,
        tier_floor,
    )
    from distributed_point_functions_tpu.serving import (
        PlainSession,
        ServingConfig,
        bucket_size,
    )

    # Stage spans accumulate process-wide; reset so the report's span
    # summary covers exactly this sweep.
    tracing.reset_stages()

    num_records = int(os.environ.get("SERVING_BENCH_RECORDS", 2048))
    record_bytes = int(os.environ.get("SERVING_BENCH_RECORD_BYTES", 32))
    num_requests = int(os.environ.get("SERVING_BENCH_REQUESTS", 64))
    max_batch = int(os.environ.get("SERVING_BENCH_MAX_BATCH", 16))
    concurrency_levels = [
        int(c)
        for c in os.environ.get("SERVING_BENCH_CONCURRENCY", "1,4,16")
        .split(",")
        if c.strip()
    ]

    _log(
        f"database: {num_records} x {record_bytes}B, "
        f"{num_requests} requests/point, max_batch={max_batch}, "
        f"concurrency sweep {concurrency_levels}"
    )
    record_list = [
        (b"serve-%06d:" % i).ljust(record_bytes, b".")[:record_bytes]
        for i in range(num_records)
    ]
    builder = DenseDpfPirDatabase.Builder()
    for r in record_list:
        builder.insert(r)
    database = builder.build()

    # Request pool: one single-key plain request per closed-loop request,
    # generated up front so key generation never sits inside the timed
    # loop. The oracle answers each request alone on a bare server — the
    # ground truth both modes must match bit-for-bit.
    import numpy as np

    rng = np.random.default_rng(11)
    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    requests = [
        client.create_plain_requests([int(i)])[0]
        for i in rng.integers(0, num_records, num_requests)
    ]
    oracle_server = DenseDpfPirServer.create_plain(database)
    _log("computing oracle responses (and warming per-shape jit entries)")
    t0 = time.perf_counter()
    oracle = [
        oracle_server.handle_plain_request(r).dpf_pir_response.masked_response
        for r in requests
    ]
    # Warm every power-of-two bucket the batcher can form, so the sweep
    # measures steady-state serving rather than first-shape compiles (the
    # module-level jit cache is shared across server instances). Warm at
    # every planner tier, not just the default: a forced-tier blackbox
    # probe running alongside traffic (the prober_overhead point below)
    # momentarily demotes concurrent batches too — the floor only
    # demotes — and an unwarmed (tier x bucket) shape would charge its
    # compile to the probed leg.
    for tier in ("materialized", "streaming", "chunked"):
        prev = tier_floor()
        set_tier_floor(tier)
        try:
            b = 1
            while b <= max_batch:
                oracle_server.handle_plain_request(
                    messages.PirRequest(
                        plain_request=messages.PlainRequest(
                            dpf_keys=list(
                                requests[0].plain_request.dpf_keys
                            ) * b
                        )
                    )
                )
                b *= 2
        finally:
            set_tier_floor(prev)
    _log(f"oracle + warmup done in {time.perf_counter() - t0:.1f}s")

    def sweep_mode(batching):
        config = ServingConfig(
            max_batch_size=max_batch,
            max_wait_ms=2.0,
            max_queue=max(256, 4 * num_requests),
            batching=batching,
        )
        points = []
        with PlainSession(database, config) as session:
            for concurrency in concurrency_levels:
                wall, lats, resps = _closed_loop(
                    session.handle_request, requests, concurrency
                )
                mismatches = sum(
                    1
                    for got, want in zip(resps, oracle)
                    if got.dpf_pir_response.masked_response != want
                )
                lats.sort()
                qps = len(requests) / wall
                points.append({
                    "mode": "batched" if batching else "unbatched",
                    "concurrency": concurrency,
                    "qps": round(qps, 2),
                    "wall_s": round(wall, 3),
                    "p50_ms": round(_percentile(lats, 0.50), 3),
                    "p95_ms": round(_percentile(lats, 0.95), 3),
                    "p99_ms": round(_percentile(lats, 0.99), 3),
                    "mismatches": mismatches,
                })
                _log(
                    f"{points[-1]['mode']:>9} c={concurrency:<3} "
                    f"{qps:8.1f} q/s  p50 {points[-1]['p50_ms']:.1f} ms  "
                    f"p95 {points[-1]['p95_ms']:.1f} ms  "
                    f"mismatches={mismatches}"
                )
            metrics = session.metrics.export()
        return points, metrics

    unbatched_points, _ = sweep_mode(batching=False)
    batched_points, batched_metrics = sweep_mode(batching=True)

    # Prober overhead: the same batched point at the highest concurrency,
    # measured back to back on one session without and with a background
    # blackbox prober at its default (bounded) duty cycle. Report-only:
    # on a noisy CPU host the delta sits inside run-to-run variance, so
    # the <2% q/s budget is reviewed from the report, not gated in CI.
    def prober_overhead_point():
        from distributed_point_functions_tpu.serving.prober import Prober

        concurrency = concurrency_levels[-1]
        period_s = float(
            os.environ.get("SERVING_BENCH_PROBER_PERIOD_S", 5.0)
        )
        config = ServingConfig(
            max_batch_size=max_batch,
            max_wait_ms=2.0,
            max_queue=max(256, 4 * num_requests),
            batching=True,
        )
        # Replay the request pool until each leg spans ~2 probe periods
        # at the q/s the sweep just measured — a window shorter than a
        # period would charge one probe cycle's full cost to the whole
        # leg instead of amortizing it at the configured cadence.
        est_qps = max(
            p["qps"]
            for p in batched_points
            if p["concurrency"] == concurrency
        )
        copies = min(
            512,
            1 + int(est_qps * 2.0 * period_s / max(1, len(requests))),
        )
        reqs = requests * copies
        want_all = oracle * copies
        with PlainSession(database, config) as session:
            prober = Prober(session, record_list, period_s=period_s)
            # One cycle outside the timing so probe-shape jit entries
            # (the forced-tier variants) are compiled before either leg.
            prober.run_cycle()
            wall_base, _, _ = _closed_loop(
                session.handle_request, reqs, concurrency
            )
            with prober:
                wall_probed, _, resps = _closed_loop(
                    session.handle_request, reqs, concurrency
                )
            mismatches = sum(
                1
                for got, want in zip(resps, want_all)
                if got.dpf_pir_response.masked_response != want
            )
            base_qps = len(reqs) / wall_base
            probed_qps = len(reqs) / wall_probed
            return {
                "concurrency": concurrency,
                "period_s": period_s,
                "requests_per_leg": len(reqs),
                "baseline_wall_s": round(wall_base, 2),
                "probed_wall_s": round(wall_probed, 2),
                "baseline_qps": round(base_qps, 2),
                "probed_qps": round(probed_qps, 2),
                "overhead_pct": round(
                    100.0 * (base_qps - probed_qps) / base_qps, 2
                ),
                "prober_cycles": prober.export()["cycles"],
                "mismatches": mismatches,
            }

    prober_overhead = prober_overhead_point()
    _log(
        f"prober overhead c={prober_overhead['concurrency']}: "
        f"{prober_overhead['baseline_qps']:.1f} -> "
        f"{prober_overhead['probed_qps']:.1f} q/s "
        f"({prober_overhead['overhead_pct']:+.1f}%, "
        f"{prober_overhead['prober_cycles']} probe cycles)"
    )

    # Digest piggyback overhead: the encrypted Leader->Helper path,
    # back to back with the critical-path digest pinned off (v1
    # envelope: no phase waterfall, no recv/send timestamps, no skew
    # merge on the Leader) and on (the v2 default). Report-only, same
    # rationale as the prober point: the <2% q/s budget is reviewed
    # from the report because the delta sits inside CPU-host variance.
    def digest_overhead_point():
        from distributed_point_functions_tpu.serving import (
            HelperSession,
            InProcessTransport,
            LeaderSession,
        )
        from distributed_point_functions_tpu.testing import encrypt_decrypt

        concurrency = concurrency_levels[-1]
        e2e_client = DenseDpfPirClient.create(
            num_records, encrypt_decrypt.encrypt
        )
        indices = [
            int(i) for i in rng.integers(0, num_records, num_requests)
        ]
        pool = [e2e_client.create_request([i]) for i in indices]

        def leg(helper_digest):
            config = ServingConfig(
                max_batch_size=max_batch,
                max_wait_ms=2.0,
                max_queue=max(256, 4 * num_requests),
                batching=True,
                helper_digest=helper_digest,
            )
            helper = HelperSession(
                database, encrypt_decrypt.decrypt, config
            )
            leader = LeaderSession(
                database, InProcessTransport(helper.handle_wire), config
            )
            with helper, leader:
                # One warm request outside the timing: the envelope
                # probe settles and the leader-share jit shapes warm.
                leader.handle_request(pool[0][0])
                wall, _, resps = _closed_loop(
                    leader.handle_request,
                    [r for r, _ in pool],
                    concurrency,
                )
            bad = 0
            for (_, state), idx, resp in zip(pool, indices, resps):
                got = e2e_client.handle_response(resp, state)
                if got != [record_list[idx]]:
                    bad += 1
            return len(pool) / wall, bad

        base_qps, base_bad = leg(helper_digest=False)
        digest_qps, digest_bad = leg(helper_digest=True)
        return {
            "concurrency": concurrency,
            "requests_per_leg": len(pool),
            "baseline_qps": round(base_qps, 2),
            "digest_qps": round(digest_qps, 2),
            "overhead_pct": round(
                100.0 * (base_qps - digest_qps) / base_qps, 2
            ),
            "mismatches": base_bad + digest_bad,
        }

    digest_overhead = digest_overhead_point()
    _log(
        f"digest overhead c={digest_overhead['concurrency']}: "
        f"{digest_overhead['baseline_qps']:.1f} -> "
        f"{digest_overhead['digest_qps']:.1f} q/s "
        f"({digest_overhead['overhead_pct']:+.1f}%)"
    )

    # Cost-ledger overhead: the same batched point at the highest
    # concurrency, back to back on two fresh sessions — one bound to a
    # ledger whose `observe` is short-circuited (the join never runs),
    # one bound to a real `CostLedger` — so the delta is exactly the
    # per-batch predicted-vs-actual join. Report-only, same <2% q/s
    # budget and CPU-variance rationale as the prober/digest points.
    def ledger_overhead_point():
        from distributed_point_functions_tpu.observability import (
            costmodel as costmodel_mod,
        )

        class _NullLedger(costmodel_mod.CostLedger):
            def observe(self, *args, **kwargs):  # noqa: D401 - no-op
                return None

        concurrency = concurrency_levels[-1]
        config = ServingConfig(
            max_batch_size=max_batch,
            max_wait_ms=2.0,
            max_queue=max(256, 4 * num_requests),
            batching=True,
        )
        prev = costmodel_mod.default_cost_ledger()

        def leg(ledger):
            costmodel_mod.set_default_cost_ledger(ledger)
            with PlainSession(database, config) as session:
                wall, _, resps = _closed_loop(
                    session.handle_request, requests, concurrency
                )
            bad = sum(
                1
                for got, want in zip(resps, oracle)
                if got.dpf_pir_response.masked_response != want
            )
            return len(requests) / wall, bad

        try:
            base_qps, base_bad = leg(_NullLedger())
            measured = costmodel_mod.CostLedger()
            ledger_qps, ledger_bad = leg(measured)
        finally:
            costmodel_mod.set_default_cost_ledger(prev)
        return {
            "concurrency": concurrency,
            "requests_per_leg": len(requests),
            "baseline_qps": round(base_qps, 2),
            "ledger_qps": round(ledger_qps, 2),
            "overhead_pct": round(
                100.0 * (base_qps - ledger_qps) / base_qps, 2
            ),
            "ledger_samples": measured.export()["total_samples"],
            "mismatches": base_bad + ledger_bad,
        }

    ledger_overhead = ledger_overhead_point()
    _log(
        f"ledger overhead c={ledger_overhead['concurrency']}: "
        f"{ledger_overhead['baseline_qps']:.1f} -> "
        f"{ledger_overhead['ledger_qps']:.1f} q/s "
        f"({ledger_overhead['overhead_pct']:+.1f}%, "
        f"{ledger_overhead['ledger_samples']} joined batches)"
    )

    # Pipeline A/B: the same batched point at the highest concurrency,
    # back to back on two fresh sessions — the serial depth-1 worker
    # (pre-pipeline behavior, bit-for-bit) vs the default depth-2
    # pipelined dispatch (bucket N dispatches while bucket N-1
    # completes). The depth-2 q/s is the gated `serving_qps_pipelined`
    # history record (direction "higher"); `overhead_pct` is the
    # report-only cost of the pipeline machinery relative to depth 1,
    # budgeted <2% and reviewed from the report (on a CPU host the
    # delta sits inside run-to-run variance, same rationale as the
    # prober/digest/ledger points). The point also captures the two
    # staging-side pipelining numbers: `staging_hidden_ms` (overlapped
    # H2D milliseconds from a pipelined full staging of the same
    # records) and `prestage_bytes_saved` (bytes a ~1%-row delta
    # rotation's prestage keeps off the bus vs its full image).
    def pipeline_point():
        from distributed_point_functions_tpu.observability.device import (
            default_telemetry,
        )

        concurrency = concurrency_levels[-1]

        def leg(depth):
            config = ServingConfig(
                max_batch_size=max_batch,
                max_wait_ms=2.0,
                max_queue=max(256, 4 * num_requests),
                batching=True,
                pipeline_depth=depth,
            )
            with PlainSession(database, config) as session:
                wall, _, resps = _closed_loop(
                    session.handle_request, requests, concurrency
                )
            bad = sum(
                1
                for got, want in zip(resps, oracle)
                if got.dpf_pir_response.masked_response != want
            )
            return len(requests) / wall, bad

        serial_qps, serial_bad = leg(1)
        pipelined_qps, pipelined_bad = leg(2)

        # Hidden transfer time: stage a fresh build of the same records
        # through the pipelined path and read the ledger's overlapped
        # delta — the milliseconds of host work performed while H2D
        # copies were already in flight.
        ledger = default_telemetry().transfers
        fresh_builder = DenseDpfPirDatabase.Builder()
        for r in record_list:
            fresh_builder.insert(r)
        fresh = fresh_builder.build()
        hidden_before = ledger.overlapped_ms("db_staging")
        _ = fresh.db_words
        hidden_ms = ledger.overlapped_ms("db_staging") - hidden_before

        # Delta-rotation savings: a build_from generation touching ~1%
        # of rows prestaged against the bench database's resident
        # staging ships only the touched rows plus the index vector.
        touched = max(1, num_records // 100)
        delta_builder = DenseDpfPirDatabase.Builder()
        for i in range(touched):
            delta_builder.update(
                i, bytes(b ^ 0x5A for b in record_list[i])
            )
        delta = delta_builder.build_from(database)
        delta.prestage()
        stats = delta.last_prestage_stats or {}

        return {
            "concurrency": concurrency,
            "requests_per_leg": len(requests),
            "serial_qps": round(serial_qps, 2),
            "pipelined_qps": round(pipelined_qps, 2),
            "overhead_pct": round(
                100.0 * (serial_qps - pipelined_qps) / serial_qps, 2
            ),
            "staging_hidden_ms": round(hidden_ms, 3),
            "prestage_mode": stats.get("mode"),
            "prestage_rows_touched": touched,
            "prestage_bytes_staged": int(stats.get("bytes_staged", 0)),
            "prestage_bytes_saved": int(stats.get("bytes_saved", 0)),
            "prestage_bytes_full_image": int(
                stats.get("bytes_full_image", 0)
            ),
            "mismatches": serial_bad + pipelined_bad,
        }

    pipeline_overhead = pipeline_point()
    _log(
        f"pipeline A/B c={pipeline_overhead['concurrency']}: depth-1 "
        f"{pipeline_overhead['serial_qps']:.1f} -> depth-2 "
        f"{pipeline_overhead['pipelined_qps']:.1f} q/s "
        f"({pipeline_overhead['overhead_pct']:+.1f}% overhead), staging "
        f"hid {pipeline_overhead['staging_hidden_ms']:.1f} ms, delta "
        f"prestage saved {pipeline_overhead['prestage_bytes_saved']} of "
        f"{pipeline_overhead['prestage_bytes_full_image']} bytes"
    )

    # Utilization A/B: the same batched point back to back with the
    # device-seconds ledger off (`ServingConfig(utilization=False)`,
    # the batcher never sees a tracker) vs on (the default). The
    # on-leg's duty cycle and bubble p99 become the gated
    # `device_duty_cycle_pct` (direction "higher") and
    # `pipeline_bubble_ms_p99` (direction "lower") history records;
    # `overhead_pct` — the throughput cost of bracketing every
    # worker/completion interval — stays report-only under the same
    # <2% budget as the other always-on telemetry points.
    def utilization_point():
        from distributed_point_functions_tpu.observability.utilization import (
            default_utilization_tracker,
        )

        concurrency = concurrency_levels[-1]
        tracker = default_utilization_tracker()

        def leg(enabled):
            tracker.reset()
            config = ServingConfig(
                max_batch_size=max_batch,
                max_wait_ms=2.0,
                max_queue=max(256, 4 * num_requests),
                batching=True,
                utilization=enabled,
            )
            with PlainSession(database, config) as session:
                wall, _, resps = _closed_loop(
                    session.handle_request, requests, concurrency
                )
            bad = sum(
                1
                for got, want in zip(resps, oracle)
                if got.dpf_pir_response.masked_response != want
            )
            return len(requests) / wall, bad

        baseline_qps, baseline_bad = leg(False)
        utilization_qps, utilization_bad = leg(True)
        totals = tracker.export()["totals"]

        return {
            "concurrency": concurrency,
            "requests_per_leg": len(requests),
            "baseline_qps": round(baseline_qps, 2),
            "utilization_qps": round(utilization_qps, 2),
            "overhead_pct": round(
                100.0 * (baseline_qps - utilization_qps) / baseline_qps,
                2,
            ),
            "duty_cycle_pct": totals["duty_cycle_pct"],
            "bubble_ms_p99": round(totals["bubble_ms_p99"], 3)
            if totals["bubble_ms_p99"] is not None
            else None,
            "bubble_causes": sorted(totals["idle_s"]),
            "bubbles": totals["bubbles"],
            "mismatches": baseline_bad + utilization_bad,
        }

    utilization_overhead = utilization_point()
    _log(
        f"utilization A/B c={utilization_overhead['concurrency']}: off "
        f"{utilization_overhead['baseline_qps']:.1f} -> on "
        f"{utilization_overhead['utilization_qps']:.1f} q/s "
        f"({utilization_overhead['overhead_pct']:+.1f}% overhead), duty "
        f"cycle {utilization_overhead['duty_cycle_pct']}%, bubble p99 "
        f"{utilization_overhead['bubble_ms_p99']} ms over "
        f"{utilization_overhead['bubbles']} bubbles"
    )

    # Mesh stage: the same closed-loop point served from a 2-D device
    # mesh (shard x key axes) behind the identical serving surface,
    # bit-checked against the same oracle. Also the donation proof:
    # TransferLedger `selection_scratch` copies before/after the timed
    # loop — with the donated scratch pool the delta is 0 while
    # `key_staging` grows by one per dispatched batch, i.e. donation
    # saves one h2d copy per steady-state batch.
    def mesh_stage_point():
        import jax

        from distributed_point_functions_tpu.observability.device import (
            default_telemetry,
        )
        from distributed_point_functions_tpu.parallel.sharded import (
            make_mesh2d,
        )

        ndev = len(jax.devices())
        if ndev < 2:
            _log(
                f"mesh stage skipped: {ndev} device(s); run directly "
                "(python -m benchmarks.serving_bench) or set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 for the CPU "
                "fallback"
            )
            return None
        key_devices = 2 if ndev % 2 == 0 else 1
        mesh = make_mesh2d(ndev // key_devices, key_devices)
        concurrency = concurrency_levels[-1]
        config = ServingConfig(
            max_batch_size=max_batch,
            max_wait_ms=2.0,
            max_queue=max(256, 4 * num_requests),
            batching=True,
        )
        ledger = default_telemetry().transfers
        with PlainSession(database, config, mesh=mesh) as session:
            # Warm outside the timing: compiles the mesh shard_map
            # entry and stages the one pooled scratch buffer.
            session.handle_request(requests[0])
            mesh_served = session.server._mesh_plan is not None
            scratch_before = ledger.copies("selection_scratch")
            keys_before = ledger.copies("key_staging")
            wall, lats, resps = _closed_loop(
                session.handle_request, requests, concurrency
            )
            scratch_after = ledger.copies("selection_scratch")
            batches = ledger.copies("key_staging") - keys_before
            mismatches = sum(
                1
                for got, want in zip(resps, oracle)
                if got.dpf_pir_response.masked_response != want
            )
            mesh_served = (
                mesh_served and session.server._mesh_plan is not None
            )
            mesh_export = session.server.mesh_export()
        lats.sort()
        qps = len(requests) / wall
        scratch_delta = scratch_after - scratch_before
        return {
            "devices": ndev,
            "mesh_shape": mesh_export.get("shape"),
            "concurrency": concurrency,
            "qps": round(qps, 2),
            "p50_ms": round(_percentile(lats, 0.50), 3),
            "p95_ms": round(_percentile(lats, 0.95), 3),
            "mismatches": mismatches,
            "mesh_served": mesh_served,
            "fallback_error": mesh_export.get("fallback_error"),
            "batches": batches,
            # Donation accounting: scratch copies staged during the
            # timed loop (0 = the donated buffer recycled every batch)
            # and the per-batch copies that recycling saved.
            "scratch_copies_before": scratch_before,
            "scratch_copies_after": scratch_after,
            "scratch_copies_during_loop": scratch_delta,
            "donation_saved_copies": max(0, batches - scratch_delta),
            "plan": mesh_export.get("plan"),
        }

    mesh_point = None
    if os.environ.get("SERVING_BENCH_MESH", "1") != "0":
        mesh_point = mesh_stage_point()
    if mesh_point:
        _log(
            f"mesh {mesh_point['mesh_shape']} c="
            f"{mesh_point['concurrency']}: {mesh_point['qps']:.1f} q/s  "
            f"p50 {mesh_point['p50_ms']:.1f} ms  "
            f"mismatches={mesh_point['mismatches']}  donation saved "
            f"{mesh_point['donation_saved_copies']} scratch copies over "
            f"{mesh_point['batches']} batches"
        )

    def sparse_point():
        """Sparse (cuckoo key-value) ladder: closed-loop string-keyed
        traffic through a `SparsePlainSession` (batcher on), every
        masked response bit-checked against an unbatched sparse oracle,
        then one ~1%-key write batch landed as a SnapshotManager delta
        rotation (prestage stats read off the staged generation)."""
        from distributed_point_functions_tpu.pir.cuckoo_database import (
            CuckooHashedDpfPirDatabase,
        )
        from distributed_point_functions_tpu.pir.sparse_client import (
            CuckooHashingSparseDpfPirClient,
            KeyNotFound,
        )
        from distributed_point_functions_tpu.pir.sparse_server import (
            CuckooHashingSparseDpfPirServer,
        )
        from distributed_point_functions_tpu.serving import (
            SnapshotManager,
            SparsePlainSession,
            make_sparse_client,
            sparse_lookup_plain,
        )

        num_keys = int(os.environ.get("SERVING_BENCH_SPARSE_KEYS", 512))
        touched = max(1, num_keys // 100)
        # Fixed-width keys/values: a delta rotation preserves the
        # packed row width of each parallel dense store, so the write
        # batch below must stay in-width to prestage as a delta.
        records = {
            b"skey-%06d" % i: (b"sval-%06d:" % i).ljust(
                record_bytes, b"."
            )[:record_bytes]
            for i in range(num_keys)
        }
        params = CuckooHashingSparseDpfPirServer.generate_params(
            num_keys, seed=b"0123456789abcdef"
        )
        builder = CuckooHashedDpfPirDatabase.Builder().set_params(params)
        for kv in records.items():
            builder.insert(kv)
        sparse_db = builder.build()

        sparse_client = CuckooHashingSparseDpfPirClient.create(
            params, lambda pt, ci: pt
        )
        key_list = sorted(records)
        sparse_requests = [
            sparse_client.create_plain_requests(
                [key_list[int(i)]]
            )[0]
            for i in rng.integers(0, num_keys, num_requests)
        ]
        sparse_oracle_server = (
            CuckooHashingSparseDpfPirServer.create_plain(
                params, sparse_db
            )
        )
        sparse_oracle = [
            sparse_oracle_server.handle_plain_request(
                r
            ).dpf_pir_response.masked_response
            for r in sparse_requests
        ]

        concurrency = concurrency_levels[-1]
        config = ServingConfig(
            max_batch_size=max_batch,
            max_wait_ms=2.0,
            max_queue=max(256, 4 * num_requests),
            batching=True,
        )
        with SparsePlainSession(params, sparse_db, config) as session:
            # Warm pass: compiles every bucket shape the closed loop
            # can form and makes the gen-0 stagings resident (the
            # prerequisite for the rotation below to prestage as a
            # delta rather than a full image).
            for r in sparse_requests:
                session.handle_request(r)
            wall, lats, resps = _closed_loop(
                session.handle_request, sparse_requests, concurrency
            )
            mismatches = sum(
                1
                for got, want in zip(resps, sparse_oracle)
                if got.dpf_pir_response.masked_response != want
            )

            # Write batch: rewrite ~1% of the keys (new in-width
            # values) plus one brand-new key, landed as a delta
            # rotation while the session stays live.
            manager = SnapshotManager(session)
            delta = CuckooHashedDpfPirDatabase.Builder()
            rewritten = key_list[:touched]
            for key in rewritten:
                delta.insert(
                    (key, records[key][::-1])  # same width, new bytes
                )
            new_key = b"snew-%06d" % num_keys
            delta.insert(
                (new_key, (b"sval-new---:").ljust(
                    record_bytes, b"."
                )[:record_bytes])
            )
            db1 = delta.build_from(sparse_db)
            staged_bytes = manager.stage(db1)
            manager.flip(timeout=120.0)
            stats = db1.last_prestage_stats or {}

            lookup_client = make_sparse_client(session)
            out = sparse_lookup_plain(
                session,
                lookup_client,
                [rewritten[0], new_key, b"skey-no-such"],
            )
            lookup_mismatches = 0
            if out[0] != records[rewritten[0]][::-1]:
                lookup_mismatches += 1
            if out[1] != (b"sval-new---:").ljust(
                record_bytes, b"."
            )[:record_bytes]:
                lookup_mismatches += 1
            if not isinstance(out[2], KeyNotFound):
                lookup_mismatches += 1
            generation = manager.serving_generation()

        lats.sort()
        return {
            "num_keys": num_keys,
            "num_buckets": params.num_buckets,
            "num_hash_functions": params.num_hash_functions,
            "concurrency": concurrency,
            "qps": round(len(sparse_requests) / wall, 2),
            "p50_ms": round(_percentile(lats, 0.50), 3),
            "p95_ms": round(_percentile(lats, 0.95), 3),
            "mismatches": mismatches + lookup_mismatches,
            "rotation_keys_touched": touched + 1,
            "rotation_staged_bytes": staged_bytes,
            "prestage_mode": stats.get("mode"),
            "prestage_bytes_saved": stats.get("bytes_saved", 0),
            "prestage_bytes_staged": stats.get("bytes_staged", 0),
            "prestage_bytes_full_image": stats.get(
                "bytes_full_image", 0
            ),
            "serving_generation": generation,
        }

    sparse_point_r = None
    if os.environ.get("SERVING_BENCH_SPARSE", "1") != "0":
        sparse_point_r = sparse_point()
    if sparse_point_r:
        _log(
            f"sparse {sparse_point_r['num_keys']} keys c="
            f"{sparse_point_r['concurrency']}: "
            f"{sparse_point_r['qps']:.1f} q/s  p50 "
            f"{sparse_point_r['p50_ms']:.1f} ms  mismatches="
            f"{sparse_point_r['mismatches']}  rotation "
            f"{sparse_point_r['prestage_mode']} saved "
            f"{sparse_point_r['prestage_bytes_saved']} of "
            f"{sparse_point_r['prestage_bytes_full_image']} bytes"
        )

    # Cost-model accuracy: the default ledger joined every terminal
    # batch the sweeps served against its admission-time price. The
    # aggregate is the samples-weighted mean of per-cell |residual_p50|
    # (signed ratio error, 0 = perfectly priced) — report-only, and
    # appended to history.jsonl by main() with direction "lower".
    from distributed_point_functions_tpu.observability import (
        costmodel as costmodel_mod,
    )

    cost_model_residual = workload_residual_summary(
        costmodel_mod.default_cost_ledger().export(), "pir"
    )
    if cost_model_residual["cells"]:
        _log(
            f"cost-model residual (pir): "
            f"|p50| {cost_model_residual['residual_p50_abs']:.3f} over "
            f"{cost_model_residual['samples']} batches in "
            f"{len(cost_model_residual['cells'])} cells"
        )

    best_batched = max(p["qps"] for p in batched_points)
    best_unbatched = max(p["qps"] for p in unbatched_points)
    correctness_ok = (
        all(
            p["mismatches"] == 0
            for p in batched_points + unbatched_points
        )
        and prober_overhead["mismatches"] == 0
        and digest_overhead["mismatches"] == 0
        and ledger_overhead["mismatches"] == 0
        and pipeline_overhead["mismatches"] == 0
        and utilization_overhead["mismatches"] == 0
        and (mesh_point is None or mesh_point["mismatches"] == 0)
        and (
            sparse_point_r is None
            or sparse_point_r["mismatches"] == 0
        )
    )
    compiles = batched_metrics["counters"].get(
        "plain.batcher.jit_bucket_compiles", 0
    )
    report = {
        "config": {
            "num_records": num_records,
            "record_bytes": record_bytes,
            "num_requests": num_requests,
            "max_batch_size": max_batch,
            "concurrency_levels": concurrency_levels,
            "jit_bucket_bound": bucket_size(max_batch).bit_length(),
        },
        "sweep": unbatched_points + batched_points,
        "best_batched_qps": best_batched,
        "best_unbatched_qps": best_unbatched,
        "batched_speedup": round(best_batched / best_unbatched, 2)
        if best_unbatched
        else None,
        "correctness_ok": correctness_ok,
        "prober_overhead": prober_overhead,
        "digest_overhead": digest_overhead,
        "ledger_overhead": ledger_overhead,
        "pipeline_overhead": pipeline_overhead,
        "utilization_overhead": utilization_overhead,
        "mesh": mesh_point,
        "sparse": sparse_point_r,
        "cost_model_residual_p50": cost_model_residual,
        "jit_bucket_compiles": compiles,
        "batched_metrics": batched_metrics,
        # Per-stage span summary (queue wait / batch assembly / device
        # compute / evaluate_* percentiles) and the planner-tier
        # counters, so the report decomposes where the q/s went.
        "stage_spans": tracing.stage_summary(),
        "runtime_counters": tracing.runtime_counters.export(),
    }
    _log(
        f"best batched {best_batched:.1f} q/s vs unbatched "
        f"{best_unbatched:.1f} q/s ({report['batched_speedup']}x), "
        f"{compiles} jit buckets, correctness "
        f"{'ok' if correctness_ok else 'FAILED'}"
    )

    out = os.environ.get(
        "SERVING_BENCH_OUT", "benchmarks/results/serving_bench.json"
    )
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        _log(f"report written to {out}")
    return report


def main():
    # Must run before anything imports jax: on a CPU-only host the
    # mesh stage needs >1 device, which XLA only fakes at init time.
    force_host_devices()
    report = run_serving_bench()
    print(json.dumps(report, indent=2))
    if os.environ.get("BENCH_HISTORY", "1") != "0":
        append_residual_history(
            report["cost_model_residual_p50"], bench="serving_bench"
        )
        append_mesh_history(report["mesh"], bench="serving_bench")
        append_pipeline_history(
            report["pipeline_overhead"], bench="serving_bench"
        )
        append_utilization_history(
            report["utilization_overhead"], bench="serving_bench"
        )
        append_sparse_history(report["sparse"], bench="serving_bench")
    if not report["correctness_ok"]:
        raise SystemExit("serving bench FAILED correctness")


if __name__ == "__main__":
    main()
