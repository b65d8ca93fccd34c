"""Automated perf-regression gate over the bench trajectory.

`bench.py` (and the satellite benches it hosts) append one structured
record per run to `benchmarks/results/history.jsonl`; this module turns
that write-only trail into an enforced contract. The newest record per
metric is compared against a **rolling median** of the last `--window`
clean runs with a **noise band** (`--band`, default 15%), and the gate
distinguishes three very different kinds of bad:

* ``regression``  — a clean run measured outside the band on the bad
                    side of the median. The only verdict that exits
                    nonzero.
* ``infra_error`` — the newest record says the *harness* failed
                    (`status != "ok"`: hung backend init, watchdog
                    stall, chip unreachable). A hung TPU init used
                    to emit a bare
                    ``value: 0.0`` indistinguishable from a
                    catastrophic real regression. Infra errors never
                    fail the gate and never pollute the median.
* ``first_run``   — not enough clean history to form a median yet.

Good news is graded too: ``ok`` (inside the band or better) and
``improved`` (outside the band on the *good* side — worth a look, but
never a failure).

History record schema (one JSON object per line; unknown fields pass
through):

    {"ts_unix": 1754380800.0,            # when the run finished
     "metric": "dense_pir_queries_per_sec_chip_1048576x256B",
     "value": 7203.53, "unit": "queries/s",
     "status": "ok",                      # "ok" | "infra_error" | "error"
     "vs_baseline": 450.2,
     "git_rev": "6cfabdc",                # best-effort
     "device": "tpu", "topology": "1x1",  # backend + device count
     "jax_version": "0.4.35",             # optional; stack stamp
     "backend": "tpu",                    # optional; stack stamp
     "error": "...",                      # failure paths only
     "last_good": 7203.53,                # failure paths: prior capture
     "p99": 12.4, "samples": 512,         # latency records only
     "direction": "higher"}               # optional; inferred from unit

Direction (is bigger better?) is inferred from the unit — throughput
units (`queries/s`, `lanes/s`, `GB/s`) are higher-is-better, time
units (`ns/leaf`, `ms`, `s`) lower-is-better — and can be pinned per
record with `direction`. Verdicts honor it on both sides: the
median comparison flips which band edge is "worse", and `vs_baseline`
(passed through to the verdict) reads as an improvement below 1.0 for
lower-is-better metrics.

Stack stamps (`jax_version`, `backend`) group the rolling median: a
prior record with a *different* stamp never enters the newest run's
median (a JAX upgrade or a CPU run must not mask a TPU regression).
Records missing a stamp — all pre-stamp history — match any stack, so
existing history keeps judging.

CLI (``python -m benchmarks.regression_gate``): exits 0 unless a real
regression is present. ``--check-only`` is the presubmit mode: same
verdicts, but an empty/missing history is "nothing to check" (exit 0)
instead of a configuration error, so the gate can run on CPU against
the committed fixture before any TPU capture exists.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import subprocess
import sys
import time
from typing import Dict, List, Optional

DEFAULT_HISTORY = os.path.join(
    os.path.dirname(__file__), "results", "history.jsonl"
)
DEFAULT_WINDOW = 5
DEFAULT_BAND = 0.15
MIN_HISTORY = 2  # clean prior runs needed before the gate judges

_HIGHER_UNITS = ("queries/s", "lanes/s", "GB/s", "GiB/s", "ops/s", "x")
_LOWER_UNITS = ("ns/leaf", "ns", "ms", "s", "bytes")


# ---------------------------------------------------------------------------
# History store
# ---------------------------------------------------------------------------


def append_record(record: dict, path: str = DEFAULT_HISTORY) -> None:
    """Append one run record (adds `ts_unix` if missing). Creates the
    store on first write. Best-effort callers (bench.py's emit path)
    wrap this in try/except — the history must never break a bench."""
    record = dict(record)
    record.setdefault("ts_unix", round(time.time(), 3))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")


def load_history(path: str) -> tuple:
    """(records, skipped_lines). Malformed lines are skipped and
    counted, never fatal — a half-written line from a killed bench
    must not take the gate down with it."""
    records: List[dict] = []
    skipped = 0
    try:
        with open(path) as f:
            lines = f.readlines()
    except FileNotFoundError:
        return [], 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            skipped += 1
            continue
        if not isinstance(rec, dict) or "metric" not in rec:
            skipped += 1
            continue
        records.append(rec)
    return records, skipped


def git_rev() -> Optional[str]:
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip()
            or None
        )
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Verdicts
# ---------------------------------------------------------------------------


def direction_of(record: dict) -> str:
    """'higher' or 'lower' — explicit field wins, else inferred from
    the unit, defaulting to higher-is-better (every headline metric in
    this repo is a throughput)."""
    explicit = record.get("direction")
    if explicit in ("higher", "lower"):
        return explicit
    unit = str(record.get("unit", ""))
    if unit in _LOWER_UNITS:
        return "lower"
    if unit in _HIGHER_UNITS:
        return "higher"
    return "higher"


def _is_clean(record: dict) -> bool:
    status = record.get("status", "ok")
    value = record.get("value")
    return (
        status == "ok"
        and isinstance(value, (int, float))
        and math.isfinite(float(value))
    )


_STACK_KEYS = ("jax_version", "backend")


def _same_stack(record: dict, stack: dict) -> bool:
    """Whether `record` may enter a median for a run stamped `stack`.
    A missing stamp on the record is a wildcard (pre-stamp history);
    a present-but-different stamp excludes it."""
    return all(record.get(k) in (None, v) for k, v in stack.items())


def judge_metric(
    records: List[dict],
    window: int = DEFAULT_WINDOW,
    band: float = DEFAULT_BAND,
) -> dict:
    """Verdict for one metric's records (oldest -> newest). The newest
    record is the run under judgment; the rolling median forms over the
    `window` most recent *clean* records before it."""
    newest = records[-1]
    verdict = {
        "metric": newest.get("metric"),
        "value": newest.get("value"),
        "unit": newest.get("unit"),
        "status": newest.get("status", "ok"),
        "git_rev": newest.get("git_rev"),
        "n_records": len(records),
    }
    if newest.get("vs_baseline") is not None:
        verdict["vs_baseline"] = newest["vs_baseline"]
        verdict["vs_baseline_direction"] = direction_of(newest)
    for k in _STACK_KEYS:
        if newest.get(k) is not None:
            verdict[k] = newest[k]
    if not _is_clean(newest):
        # Harness failure, not a measurement: report, carry the
        # last-good context forward, never fail the gate.
        verdict.update(
            verdict="infra_error",
            reason=str(
                newest.get("error", "run reported a non-ok status")
            )[:300],
            last_good=newest.get("last_good"),
        )
        return verdict
    stack = {
        k: newest.get(k) for k in _STACK_KEYS
        if newest.get(k) is not None
    }
    prior_clean = [
        r for r in records[:-1]
        if _is_clean(r) and _same_stack(r, stack)
    ][-window:]
    if len(prior_clean) < MIN_HISTORY:
        verdict.update(
            verdict="first_run",
            reason=(
                f"only {len(prior_clean)} clean prior run(s)"
                + (" on this stack" if stack else "")
                + f"; need {MIN_HISTORY} to judge"
            ),
        )
        return verdict
    median = statistics.median(float(r["value"]) for r in prior_clean)
    value = float(newest["value"])
    direction = direction_of(newest)
    verdict.update(
        median=round(median, 4),
        band=band,
        window=len(prior_clean),
        direction=direction,
    )
    if median == 0:
        verdict.update(
            verdict="ok", reason="zero median; nothing to compare against"
        )
        return verdict
    ratio = value / median
    delta_pct = round((ratio - 1.0) * 100, 2)
    verdict["delta_pct"] = delta_pct
    worse = ratio < (1.0 - band) if direction == "higher" else ratio > (
        1.0 + band
    )
    better = ratio > (1.0 + band) if direction == "higher" else ratio < (
        1.0 - band
    )
    if worse:
        verdict.update(
            verdict="regression",
            reason=(
                f"{value} vs rolling median {round(median, 2)} "
                f"({delta_pct:+}% with a ±{band:.0%} noise band, "
                f"{direction} is better)"
            ),
        )
    elif better:
        verdict.update(
            verdict="improved",
            reason=f"{delta_pct:+}% vs rolling median {round(median, 2)}",
        )
    else:
        verdict.update(
            verdict="ok",
            reason=f"{delta_pct:+}% within the ±{band:.0%} noise band",
        )
    return verdict


def gate(
    records: List[dict],
    window: int = DEFAULT_WINDOW,
    band: float = DEFAULT_BAND,
    metrics: Optional[List[str]] = None,
) -> List[dict]:
    """One verdict per metric present in `records` (filtered to
    `metrics` when given), judging each metric's newest record."""
    by_metric: Dict[str, List[dict]] = {}
    for rec in records:
        name = str(rec.get("metric"))
        if metrics and name not in metrics:
            continue
        by_metric.setdefault(name, []).append(rec)
    return [
        judge_metric(recs, window=window, band=band)
        for _, recs in sorted(by_metric.items())
    ]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmarks.regression_gate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--history", default=DEFAULT_HISTORY,
        help=f"history.jsonl path (default: {DEFAULT_HISTORY})",
    )
    ap.add_argument(
        "--window", type=int, default=DEFAULT_WINDOW,
        help="rolling-median window of clean prior runs (default 5)",
    )
    ap.add_argument(
        "--band", type=float, default=DEFAULT_BAND,
        help="relative noise band around the median (default 0.15)",
    )
    ap.add_argument(
        "--metric", action="append", default=None,
        help="judge only this metric (repeatable; default: all)",
    )
    ap.add_argument(
        "--check-only", action="store_true",
        help="presubmit mode: an absent/empty history is 'nothing to "
        "check' (exit 0) instead of a configuration error",
    )
    ap.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit the verdict table as one JSON document",
    )
    args = ap.parse_args(argv)

    records, skipped = load_history(args.history)
    if skipped:
        print(
            f"regression_gate: WARNING skipped {skipped} malformed "
            f"line(s) in {args.history}",
            file=sys.stderr,
        )
    if not records:
        if args.check_only:
            print(
                f"regression_gate: no history at {args.history}; "
                "nothing to check (check-only mode)"
            )
            return 0
        print(
            f"regression_gate: no usable history at {args.history}",
            file=sys.stderr,
        )
        return 2

    verdicts = gate(
        records, window=args.window, band=args.band, metrics=args.metric
    )
    if args.metric:
        missing = set(args.metric) - {v["metric"] for v in verdicts}
        for name in sorted(missing):
            print(
                f"regression_gate: WARNING metric {name!r} has no "
                "history records",
                file=sys.stderr,
            )

    if args.as_json:
        print(json.dumps({"verdicts": verdicts}, indent=2))
    else:
        for v in verdicts:
            print(
                f"regression_gate: {v['verdict']:<10} {v['metric']} "
                f"value={v['value']} {v.get('reason', '')}"
            )

    regressions = [v for v in verdicts if v["verdict"] == "regression"]
    infra = [v for v in verdicts if v["verdict"] == "infra_error"]
    summary = (
        f"regression_gate: {len(verdicts)} metric(s) judged — "
        f"{len(regressions)} regression(s), {len(infra)} infra error(s)"
    )
    print(summary, file=sys.stderr if regressions else sys.stdout)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
