"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/harness/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process loads the cell's configuration and traffic mix by name
(cell.py), builds its data and requests from `--seed`, warms up every
shape the window will use, measures for `--seconds`, checks every
answer of the window against the plain reference, and prints one JSON
object:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` the window runs under the JAX profiler and the metrics are
its per-layer ones, read from the trace (trace_reduce.py), the
harness's timers and the program's counters. `checks` holds each number
compared with its limit; they are also the last lines on stderr.

No TPU, or fewer chips than the cell asks for: exit 3 and no result.
The persistent compile cache is `<checkout>/.jax_cache` (`--cache-dir`
moves it), so only a checkout's first run of a cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

import cell as cell_mod  # noqa: E402
import trace_reduce  # noqa: E402

DEFAULT_CACHE = os.path.join(ROOT, ".jax_cache")
NO_CHIP = 3
# A traced run profiles the last seconds of its window only: the trace
# of a served window grows by ~37 MB a second on the chip, and writing
# and reading it back takes longer than the window itself.
TRACE_SECONDS = 2.0


def log(msg: str) -> None:
    print(f"[harness {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


class NoChip(RuntimeError):
    pass


def use_compile_cache(path: str) -> None:
    """Every program persists, at a fixed path: the program reads
    `JAX_COMPILATION_CACHE_DIR` and sets no other when it is set."""
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # No size cap: a capped cache evicts programs a later run needs, and
    # its file lock times out under the warm-up's concurrent compiles.
    jax.config.update("jax_compilation_cache_max_size", -1)


class GcPauses:
    """Garbage-collector pauses while armed: a stop of every Python
    thread that the window's tail latency sees."""

    def __init__(self):
        self.pauses = []
        self.armed = False
        self._t0 = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.armed and self._t0 is not None:
            self.pauses.append(
                (info["generation"], (time.perf_counter() - self._t0) * 1e3)
            )

    def close(self):
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        full = [ms for gen, ms in self.pauses if gen == 2]
        return (f"{len(self.pauses)} collections, {len(full)} full; longest "
                f"{max((ms for _, ms in self.pauses), default=0.0):.1f} ms, "
                f"total {sum(ms for _, ms in self.pauses):.1f} ms")


class CompileCounter:
    """XLA compilations while armed, from JAX's own monitoring event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        self._lock = threading.Lock()

        def listener(event, duration_secs, **kwargs):
            if self.armed and event == self.EVENT:
                with self._lock:
                    self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def prepare(driver) -> None:
    """Set-up: the driver's data and sessions, the warm-up of every shape
    its window uses, then a full garbage collection, so that the first
    full collection after the warm-up's tracing runs here and not in the
    window (a fresh process otherwise stalled for 2-12 s early in its
    first window on the chip)."""
    driver.setup()
    driver.warm()
    gc.collect()


def _merge(base: dict, extra: dict | None) -> dict:
    out = dict(base)
    for key, value in (extra or {}).items():
        out[key] = (
            _merge(out[key], value)
            if isinstance(value, dict) and isinstance(out.get(key), dict)
            else value
        )
    return out


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if require_tpu and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(
            f"the cell needs {chips} TPU chip(s); JAX found {info}"
        )
    return info


def memory_peak(chips: int):
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(config_name: str, traffic_name: str, seed: int, seconds: float,
             trace: bool, metric_entries: list, chips: int = 1,
             require_tpu: bool = True, overrides: dict | None = None,
             control: bool = False, keep_trace: str | None = None,
             cache_dir: str = DEFAULT_CACHE) -> dict:
    """One run of one cell; returns the result object."""
    device = device_info(chips, require_tpu)
    use_compile_cache(cache_dir)
    log(f"device: {device}")
    overrides = overrides or {}
    config = _merge(cell_mod.config(config_name), overrides.get("config"))
    traffic = _merge(cell_mod.traffic(traffic_name), overrides.get("traffic"))
    peaks = (
        cell_mod.peaks(device["kind"]) if device["platform"] == "tpu" else None
    )
    driver = cell_mod.driver(traffic["driver"]).Driver(
        config, traffic, seed, log, control=control
    )
    compiles = CompileCounter()
    pauses = GcPauses()
    prepare(driver)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.1f} s; window {seconds} s, trace={int(trace)}")

    import jax

    trace_dir = tempfile.mkdtemp(prefix="harness-trace-") if trace else None
    traced = None
    compiles.armed = pauses.armed = True
    try:
        if trace:
            traced = _trace_last(driver, trace_dir, seconds,
                                 min(seconds, TRACE_SECONDS))
        try:
            window = driver.window(seconds)
        finally:
            if trace:
                for timer in traced["timers"]:
                    timer.join()
                if "t_start" in traced:
                    jax.profiler.stop_trace()
        if trace and "error" in traced:
            raise traced["error"]
        compiles.armed = pauses.armed = False
        pauses.close()
        peak = memory_peak(chips)
        log(f"backend compiles inside the window: {compiles.count}")
        log(f"garbage collection inside the window: {pauses.summary()}")
        for name, party in window.get("batchers", {}).items():
            log(f"{name} batcher bucket compiles inside the window: "
                f"{party['jit_bucket_compiles']}")
        late = sorted(window.get("late_ms", []))
        if late:
            log(f"generator lateness: p50 {late[len(late) // 2]:.3f} ms, "
                f"max {late[-1]:.3f} ms over {len(late)} sends")
        latencies = sorted(window.get("latencies_ms", []))
        if latencies:
            log("latency: " + ", ".join(
                f"p{q} {latencies[min(len(latencies) - 1, len(latencies) * q // 100)]:.3f} ms"
                for q in (50, 90, 95, 99)
            ) + f", max {latencies[-1]:.3f} ms over {len(latencies)} answers")
        checks = driver.check(window)
        driver.close()
        reduction = None
        if trace:
            reduction = _reduce(trace_dir, keep_trace, require_tpu)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    record = {"setup_s": setup_s, "window": window, "trace": reduction,
              "traced": traced, "peaks": peaks, "device": device}
    metrics = {}
    for entry in metric_entries:
        value = cell_mod.metric(entry["name"]).read(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    result = {
        "correct": all(value <= limit for _, value, limit in checks),
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_ns / 1e9
        device["window_s"] = reduction.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduction.top_ops()],
            "idle_gaps": [list(x) for x in reduction.top_gaps()],
        }
    result["checks"] = {
        name: {"value": value, "limit": limit} for name, value, limit in checks
    }
    return result


def _mark() -> None:
    """An instant host annotation: the traced window's edges."""
    import jax

    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
        pass


def _trace_last(driver, trace_dir: str, window_s: float, seconds: float):
    """Profile the last `seconds` of a `window_s` window that starts now:
    a timer starts the trace and a second one marks its end at the
    window's close, each taking the driver's counters. The caller stops
    the trace once the window has drained: stopping it while requests
    are in flight took 100-165 s on the chip host, against 360 s a run.
    Returns what the trace metrics read beside the trace: the counter
    deltas over the traced span and its ends on the host clock."""
    import jax

    counters = getattr(driver, "counters", None)
    traced = {"seconds": seconds}

    def start():
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            _mark()
            traced["t_start"] = time.perf_counter()
            traced["before"] = counters() if counters else None
        except Exception as e:  # noqa: BLE001 - raised on the main thread
            traced["error"] = e

    def end():
        if "t_start" not in traced:
            return
        if counters:
            traced["counters"] = driver.delta(counters(), traced["before"])
        traced["t_stop"] = time.perf_counter()
        _mark()

    traced["timers"] = [
        threading.Timer(window_s - seconds, start),
        threading.Timer(window_s, end),
    ]
    for timer in traced["timers"]:
        timer.start()
    return traced


def _reduce(trace_dir: str, keep: str | None, require_tpu: bool):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(paths[0], keep)
    t0 = time.perf_counter()
    try:
        reduction = trace_reduce.reduce_file(paths[0])
    except ValueError as e:
        if require_tpu:
            raise
        log(f"trace not reduced: {e}")
        return None
    log(f"trace reduced in {time.perf_counter() - t0:.1f} s: busy "
        f"{reduction.busy_ns / 1e9:.4f} s of {reduction.window_ns / 1e9:.4f}"
        f" s on {reduction.devices} device(s)")
    ranked = sorted(reduction.ops.items(), key=lambda kv: -kv[1].ns)
    for name, total in ranked[:25]:
        log(f"  op {name!r} module {total.module!r}: {total.ns / 1e6:.3f} ms"
            f" in {total.count} runs")
    for name, ns in sorted(reduction.modules.items(), key=lambda kv: -kv[1]):
        log(f"  module {name!r}: {ns / 1e6:.3f} ms")
    return reduction


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cache-dir", default=DEFAULT_CACHE,
                    help="persistent compile cache (default: "
                    "<checkout>/.jax_cache)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb of a --trace 1 run here")
    args = ap.parse_args(argv)

    bench = cell_mod.benchmark()
    entry = cell_mod.workload(bench, args.workload)
    section = "per_layer" if args.trace else "end_to_end"
    try:
        result = run_cell(
            entry["config"], entry["traffic"], args.seed, args.seconds,
            bool(args.trace), cell_mod.cell_metrics(bench, entry["name"], section),
            chips=int(entry["chips"]), keep_trace=args.keep_trace,
            cache_dir=args.cache_dir,
        )
    except NoChip as e:
        log(str(e))
        return NO_CHIP
    for name, check in result["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
