"""Overload benchmark: goodput vs offered load through admission control.

Drives a `serving.PlainSession` with cost-aware admission enabled
(`capacity/admission.py`) through an offered-load ladder — closed-loop
client threads at 1x the measured saturation concurrency, then the
over-capacity points — and reports **goodput**: requests completed
within their deadline, per second. The claim under test is the PR 8
overload contract: past saturation the excess is shed at admission with
a `RetryAfter` hint (costing the server almost nothing), so goodput
stays flat instead of collapsing into queue-drain timeouts.

Every completed response is compared bit-for-bit against an oracle
computed upfront on a bare `DenseDpfPirServer`, so the goodput claim
carries the usual equal-correctness proof.

Run directly (one JSON report on stdout, also written to
``benchmarks/results/overload_bench.json``; appends one
``serving_overload_goodput_queries_per_sec`` record — ``direction:
higher`` — to the regression-gate history)::

    JAX_PLATFORMS=cpu python -m benchmarks.overload_bench

or through the headline harness (one bench-style JSON line)::

    JAX_PLATFORMS=cpu BENCH_OVERLOAD=1 python bench.py

The request pool comes from `benchmarks/workload_gen.py` profiles
(``--profile`` / OVERLOAD_BENCH_PROFILE). The default ``uniform``
reproduces the retired inline generator byte-for-byte so the goodput
history stays comparable; other profiles (``zipf``, ``diurnal``,
``bursty``, ``mixed``) record their own suffixed history series.

Environment knobs: OVERLOAD_BENCH_RECORDS (default 1024),
OVERLOAD_BENCH_RECORD_BYTES (32), OVERLOAD_BENCH_BASE_THREADS (8),
OVERLOAD_BENCH_MULTIPLIERS ("1,2"), OVERLOAD_BENCH_SECONDS (2.0 per
point), OVERLOAD_BENCH_DEADLINE_MS (1000), OVERLOAD_BENCH_BUDGET_MS
(admission queue cost budget, 250), OVERLOAD_BENCH_PROFILE (uniform),
OVERLOAD_BENCH_OUT (report path; empty string disables the file).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _log(msg: str) -> None:
    print(f"[overload-bench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _load_point(session, requests, oracle, num_threads, duration_s,
                deadline_s):
    """Closed-loop threads hammering `session` for `duration_s`; sheds
    retry after the server's hint. Returns the point stats."""
    from distributed_point_functions_tpu.serving import Overloaded

    lock = threading.Lock()
    stats = {
        "completed": 0, "shed": 0, "deadline_missed": 0,
        "mismatches": 0, "other_errors": 0,
    }
    stop = time.monotonic() + duration_s

    def worker(tid):
        i = tid
        while time.monotonic() < stop:
            request, want = requests[i % len(requests)], (
                oracle[i % len(requests)]
            )
            i += num_threads
            try:
                response = session.handle_request(
                    request, deadline=time.monotonic() + deadline_s
                )
                ok = (
                    response.dpf_pir_response.masked_response == want
                )
                with lock:
                    stats["completed"] += 1
                    if not ok:
                        stats["mismatches"] += 1
            except Overloaded as e:
                with lock:
                    stats["shed"] += 1
                time.sleep(min(max(e.retry_after_s, 1e-3), 0.05))
            except TimeoutError:
                with lock:
                    stats["deadline_missed"] += 1
            except Exception:  # noqa: BLE001 - counted, bench continues
                with lock:
                    stats["other_errors"] += 1

    threads = [
        threading.Thread(target=worker, args=(t,), name=f"load-{t}")
        for t in range(num_threads)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    stats["threads"] = num_threads
    stats["wall_s"] = round(wall, 3)
    stats["goodput_qps"] = round(stats["completed"] / wall, 2)
    offered = stats["completed"] + stats["shed"] + stats["deadline_missed"]
    stats["offered_qps"] = round(offered / wall, 2)
    stats["shed_ratio"] = round(
        stats["shed"] / offered, 4) if offered else 0.0
    return stats


def run_overload_bench():
    """Build the database, walk the offered-load ladder, return the
    report dict (also written to OVERLOAD_BENCH_OUT unless empty)."""
    from distributed_point_functions_tpu.pir import messages
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )
    from distributed_point_functions_tpu.pir.server import DenseDpfPirServer
    from distributed_point_functions_tpu.serving import (
        PlainSession,
        ServingConfig,
    )

    num_records = int(os.environ.get("OVERLOAD_BENCH_RECORDS", 1024))
    record_bytes = int(os.environ.get("OVERLOAD_BENCH_RECORD_BYTES", 32))
    base_threads = int(os.environ.get("OVERLOAD_BENCH_BASE_THREADS", 8))
    multipliers = [
        float(m)
        for m in os.environ.get("OVERLOAD_BENCH_MULTIPLIERS", "1,2")
        .split(",")
        if m.strip()
    ]
    duration_s = float(os.environ.get("OVERLOAD_BENCH_SECONDS", 2.0))
    deadline_s = (
        float(os.environ.get("OVERLOAD_BENCH_DEADLINE_MS", 1000.0)) / 1e3
    )
    budget_ms = float(os.environ.get("OVERLOAD_BENCH_BUDGET_MS", 250.0))

    profile_name = os.environ.get("OVERLOAD_BENCH_PROFILE", "uniform")
    _log(
        f"database: {num_records} x {record_bytes}B, base "
        f"{base_threads} threads, multipliers {multipliers}, "
        f"{duration_s}s/point, deadline {deadline_s * 1e3:.0f} ms, "
        f"cost budget {budget_ms:.0f} ms, profile {profile_name}"
    )
    builder = DenseDpfPirDatabase.Builder()
    for i in range(num_records):
        builder.insert(
            (b"load-%06d:" % i).ljust(record_bytes, b".")[:record_bytes]
        )
    database = builder.build()

    from benchmarks import workload_gen

    profile = workload_gen.PROFILES[profile_name]
    # The `uniform` profile reproduces this bench's retired inline pool
    # byte-for-byte (numpy seed 8, one integers() draw of 32), so the
    # goodput history stays comparable across the generator handoff.
    indices = workload_gen.key_pool(profile, num_records)
    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    requests = [
        client.create_plain_requests([int(i)])[0] for i in indices
    ]
    oracle_server = DenseDpfPirServer.create_plain(database)
    _log("computing oracle responses and warming jit buckets")
    t0 = time.perf_counter()
    oracle = [
        oracle_server.handle_plain_request(r).dpf_pir_response.masked_response
        for r in requests
    ]
    max_batch = 16
    b = 1
    while b <= max_batch:
        oracle_server.handle_plain_request(
            messages.PirRequest(
                plain_request=messages.PlainRequest(
                    dpf_keys=list(requests[0].plain_request.dpf_keys) * b
                )
            )
        )
        b *= 2
    _log(f"oracle + warmup done in {time.perf_counter() - t0:.1f}s")

    config = ServingConfig(
        max_batch_size=max_batch,
        max_wait_ms=2.0,
        admission_enabled=True,
        admission_queue_budget_ms=budget_ms,
    )
    points = []
    with PlainSession(database, config) as session:
        for mult in multipliers:
            threads = max(1, int(round(base_threads * mult)))
            point = _load_point(
                session, requests, oracle, threads, duration_s, deadline_s
            )
            point["offered_multiplier"] = mult
            points.append(point)
            _log(
                f"x{mult:<4} ({threads:>3} threads): goodput "
                f"{point['goodput_qps']:8.1f} q/s, offered "
                f"{point['offered_qps']:8.1f} q/s, shed "
                f"{point['shed_ratio'] * 100:5.1f}%, "
                f"mismatches={point['mismatches']}"
            )
        admission_export = session.admission.export()
        metrics = session.metrics.export()

    saturation = points[0]["goodput_qps"] if points else 0.0
    worst = min((p["goodput_qps"] for p in points), default=0.0)
    correctness_ok = all(
        p["mismatches"] == 0 and p["other_errors"] == 0 for p in points
    )
    report = {
        "config": {
            "profile": profile.name,
            "num_records": num_records,
            "record_bytes": record_bytes,
            "base_threads": base_threads,
            "multipliers": multipliers,
            "seconds_per_point": duration_s,
            "deadline_ms": deadline_s * 1e3,
            "queue_budget_ms": budget_ms,
        },
        "ladder": points,
        "saturation_goodput_qps": saturation,
        "overloaded_goodput_qps": points[-1]["goodput_qps"]
        if points else 0.0,
        "goodput_retention": round(worst / saturation, 4)
        if saturation else 0.0,
        "correctness_ok": correctness_ok,
        "admission": admission_export,
        "shed_counters": {
            k: v
            for k, v in metrics["counters"].items()
            if "shed" in k or "expired" in k
        },
    }
    _log(
        f"goodput retention at x{multipliers[-1] if multipliers else '?'}: "
        f"{report['goodput_retention'] * 100:.1f}% of saturation "
        f"({report['overloaded_goodput_qps']:.1f} / {saturation:.1f} q/s), "
        f"correctness {'ok' if correctness_ok else 'FAILED'}"
    )

    out = os.environ.get(
        "OVERLOAD_BENCH_OUT", "benchmarks/results/overload_bench.json"
    )
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        _log(f"report written to {out}")
    return report


def _append_history_record(report):
    """One goodput-under-overload record for the regression gate.
    Explicit `direction: higher` (goodput dropping is the regression,
    whatever the unit inference says). Best-effort like every history
    append."""
    try:
        from benchmarks.regression_gate import append_record, git_rev

        metric = "serving_overload_goodput_queries_per_sec"
        profile = report.get("config", {}).get("profile", "uniform")
        if profile != "uniform":
            # Non-uniform profiles track their own history series; the
            # uniform rolling median must not drift on a zipf run.
            metric = f"{metric}_{profile}"
        append_record({
            "metric": metric,
            "value": report["overloaded_goodput_qps"],
            "unit": "queries/s",
            "direction": "higher",
            "vs_baseline": report["goodput_retention"],
            "status": "ok" if report["correctness_ok"] else "error",
            "git_rev": git_rev(),
            "device": os.environ.get("BENCH_PLATFORM", "cpu"),
        }, path=os.environ.get(
            "BENCH_HISTORY_PATH", "benchmarks/results/history.jsonl"
        ))
    except Exception as e:  # noqa: BLE001 - history must not break a bench
        _log(f"history append failed (non-fatal): {e}")


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--profile",
        default=os.environ.get("OVERLOAD_BENCH_PROFILE", "uniform"),
        choices=sorted(_profile_names()),
        help="workload_gen profile for the request pool "
             "(uniform = the pre-profile history-compatible pool)",
    )
    args = parser.parse_args(argv)
    os.environ["OVERLOAD_BENCH_PROFILE"] = args.profile
    report = run_overload_bench()
    if os.environ.get("BENCH_HISTORY", "1") != "0":
        _append_history_record(report)
    print(json.dumps(report, indent=2))
    if not report["correctness_ok"]:
        raise SystemExit("overload bench FAILED correctness")


def _profile_names():
    from benchmarks import workload_gen

    return workload_gen.PROFILES.keys()


if __name__ == "__main__":
    main()
