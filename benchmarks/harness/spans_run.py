"""One traced run of a cell with the program's span log over the whole
window, and every span and scope metric read from it.

    python3 benchmarks/harness/spans_run.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file>] [--cache-dir <dir>]

The same run as `run.py --trace 1` (run.py's own code, unchanged), with
what run.py does not record yet:

* the span log starts when the window starts (after set-up) and is
  drained after the run: record key `spans`;
* each window mark also takes `perf_counter_ns()` inside its
  `harness.window` annotation: `marks_ns`, the log clock at the marks;
* the raw trace is kept for the scope reader: `xplane`.

Prints run.py's result line, then one JSON line (also written to
`--out`): the span and scope metrics over that record (`metrics`), the
clock check (offsets at the two marks, and the share of logged spans
that start within 100 us of their profiler annotation), device time by
scope and idle time by span name, the longest quiet intervals of the
log with the spans open across them, the longest span of each name,
and the `helper.request` median beside `helper_leg_p50_ms.served`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (puts the checkout on sys.path)

import spanlog  # noqa: E402
import trace_reduce  # noqa: E402
import xplane_scopes  # noqa: E402

from distributed_point_functions_tpu.observability import tracing  # noqa: E402

METRICS = (
    "queue_wait_p50_ms.served", "key_staging_max_ms.served",
    "helper_host_p50_ms.served", "expand_device_us_per_key.served",
    "idle_attributed_pct.served", "leaf_values_share_pct.dpf",
    "host_dispatch_us_per_eval.dpf",
)


def _instrument(state: dict) -> None:
    """Wrap run.py's set-up, window marks and metric loader (module
    attributes, looked up at call time) to start the log, take the marks
    on the log clock and keep the record."""
    prepare, metric = run.prepare, run.cell_mod.metric

    def prepare_then_start(driver):
        prepare(driver)
        tracing.span_log.start()
        state["window_start_ns"] = time.perf_counter_ns()

    def mark():
        import jax

        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            state["marks_ns"].append(time.perf_counter_ns())

    def metric_keeping_record(name):
        module = metric(name)

        class Reader:
            @staticmethod
            def read(record):
                state["record"] = record
                return module.read(record)

        return Reader

    run.prepare = prepare_then_start
    run._mark = mark
    run.cell_mod.metric = metric_keeping_record
    state["marks_ns"] = []


def report(record: dict, metrics: list) -> dict:
    out = {"metrics": {}, "spans": len(record["spans"]["records"]),
           "dropped": record["spans"]["dropped"]}
    for name in metrics:
        try:
            value = run.cell_mod.metric(name).read(record)
        except Exception as e:  # noqa: BLE001 - reported, not fatal
            value = f"error: {type(e).__name__}: {e}"
        out["metrics"][name] = value
    clock = spanlog.clock(record)
    if clock is not None:
        profile = spanlog.profile(record)
        spans = record["spans"]["records"]
        annotations = xplane_scopes.host_events(
            profile, {r["name"] for r in spans}
        )
        matched, compared = xplane_scopes.annotation_agreement(
            spans, annotations, clock["offset_ns"]
        )
        out["clock"] = dict(
            clock, annotated_spans=compared,
            within_100us_pct=100.0 * matched / compared if compared else None,
        )
        try:
            scopes, window = spanlog.scopes(record), spanlog.window(record)
        except ValueError as e:  # a trace with no device plane
            out["scope_error"] = str(e)
        else:
            out["scope_ms"] = {
                k: v / 1e6 for k, v in sorted(scopes.totals(window).items(),
                                              key=lambda kv: -kv[1])[:20]
            }
            attributed, idle, by_name = xplane_scopes.attribute_idle(
                scopes.gaps(window), spans, clock["offset_ns"]
            )
            out["idle_ms_by_span"] = {
                str(k): v / 1e6 for k, v in sorted(by_name.items(),
                                                   key=lambda kv: -kv[1])[:20]
            }
    spans = record["spans"]["records"]
    t0 = record["spans"]["started_ns"] or 0
    out["quiet"] = [
        dict(q, at_s=(q.pop("start_ns") - t0) / 1e9)
        for q in spanlog.quiet_intervals(spans)
    ]
    longest = {}
    for r in spans:
        ms = (r["end_ns"] - r["start_ns"]) / 1e6
        if ms > longest.get(r["name"], (0.0,))[0]:
            longest[r["name"]] = (ms, (r["start_ns"] - t0) / 1e9)
    # {name: [ms, seconds into the window]} of each name's longest span.
    out["longest"] = dict(sorted(longest.items(), key=lambda kv: -kv[1][0]))
    traced = record.get("traced") or {}
    if "t_start" in traced:
        lo, hi = traced["t_start"] * 1e9, traced["t_stop"] * 1e9
        roots = [
            (r["end_ns"] - r["start_ns"]) / 1e6
            for r in record["spans"]["records"]
            if r["name"] == "helper.request" and lo <= r["end_ns"] <= hi
        ]
        if roots:
            out["helper_request_p50_ms_traced"] = statistics.median(roots)
            out["helper_leg_p50_ms_traced"] = run.cell_mod.metric(
                "helper_leg_p50_ms.served"
            ).read(record)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cache-dir", default=run.DEFAULT_CACHE)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    state: dict = {}
    _instrument(state)
    bench = run.cell_mod.benchmark()
    entry = run.cell_mod.workload(bench, args.workload)
    keep = tempfile.mkdtemp(prefix="spans-run-")
    try:
        result = run.run_cell(
            entry["config"], entry["traffic"], args.seed, args.seconds, True,
            run.cell_mod.cell_metrics(bench, entry["name"], "per_layer"),
            chips=int(entry["chips"]), keep_trace=keep,
            cache_dir=args.cache_dir,
        )
    except run.NoChip as e:
        run.log(str(e))
        return run.NO_CHIP
    print(json.dumps(result), flush=True)
    record = dict(state["record"])
    record["spans"] = tracing.span_log.drain()
    record["marks_ns"] = state["marks_ns"]
    record["xplane"] = os.path.join(keep, os.listdir(keep)[0])
    t0 = time.perf_counter()
    try:
        out = report(record, list(METRICS))
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    out.update(workload=args.workload, seed=args.seed,
               window_start_ns=state["window_start_ns"],
               report_s=time.perf_counter() - t0)
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
