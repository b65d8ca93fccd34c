"""Device-level telemetry: compile events and HBM residency.

The paper's claim is that DPF expansion and the PIR inner product run
at hardware speed — which makes *compile behavior* (how many distinct
XLA programs a process builds, how long each build takes, whether a
request hits an existing executable) and *HBM residency* (live bytes
while staging the database vs. holding a selection matrix vs. caching
cut states) first-class serving concerns, not profiler trivia. After
PR 4 the observability stack stopped at request spans; this module is
the device layer underneath it:

* `CompileTracker` — wraps the jitted entry points the serving path
  dispatches through (`pir/server.py`, `serving/batcher.py`,
  `heavy_hitters/aggregator.py`). Each dispatch site reports an
  abstract *shape key* (the jit specialization signature: batch
  bucket, block count, chunk width); the tracker counts exactly one
  compile per new (site, shape) pair, a cache hit per re-dispatch,
  and records the first-call latency into a per-site compile
  histogram. Where `jax.monitoring` exists, a process-wide listener
  additionally folds JAX's own compile-event durations in, so
  tracker-invisible compilations (donated shards, collectives) still
  show up.
* `HbmAccountant` — samples `device.memory_stats()` (TPU) or the sum
  over `jax.live_arrays()` (CPU fallback) into live-bytes watermark
  gauges with per-phase attribution: code brackets a phase with
  `accountant.phase("db_staging")`, samples inside the bracket raise
  that phase's watermark monotonically, and re-entering the phase
  resets it — so `/statusz` shows the peak HBM footprint *of each
  phase's most recent occurrence*, not a process-lifetime max that
  one cold staging pass pins forever.

Both mirror into a duck-typed metrics registry (anything with
`counter`/`gauge`/`histogram(name, labels=...)` — in production the
`serving/metrics.py` registry) but keep their own authoritative state,
so `/statusz` and tests read consistent numbers even across registry
resets. The layer DAG still holds: this module imports only `utils/`,
`robustness/` (the stdlib-only fault-injection layer beneath it) and
stdlib at module scope (JAX is reached lazily inside functions and
only when the caller asks for device facts), and `tools/check_layers.py`
pins `device.py`/`slo.py` near the bottom — no serving/pir imports,
ever.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from ..robustness import failpoints

__all__ = [
    "CompileTracker",
    "HbmAccountant",
    "TransferLedger",
    "DeviceTelemetry",
    "default_telemetry",
    "device_memory_bytes",
    "set_default_telemetry",
    "shape_key",
    "install_jax_monitoring_listener",
]


def shape_key(*parts) -> str:
    """Canonical shape-key string for a jit specialization signature.

    Accepts ints, strings, arrays (anything with `.shape`/`.dtype`),
    and nested tuples; renders to a short label-safe token (no
    `,={}` — the registry's reserved label characters), e.g.
    `shape_key(("q", 64), ("b", 8192))` -> `"q64.b8192"`.
    """
    toks = []
    for part in parts:
        if isinstance(part, tuple) and len(part) == 2 and isinstance(
            part[0], str
        ):
            prefix, value = part
        else:
            prefix, value = "", part
        if hasattr(value, "shape") and hasattr(value, "dtype"):
            dims = "x".join(str(d) for d in value.shape)
            toks.append(f"{prefix}{dims or 'scalar'}.{value.dtype}")
        else:
            toks.append(f"{prefix}{value}")
    key = ".".join(toks) or "default"
    for c in ",={}":
        key = key.replace(c, "_")
    return key


class _Dispatch:
    """What `CompileTracker.dispatch` yields: where the caller hands in
    the timing of a span over the same call."""

    __slots__ = ("timing",)

    def __init__(self):
        self.timing = None


class CompileTracker:
    """Per-site compile/dispatch accounting keyed by shape signature.

    A *site* is one logical jit entry point ("pir.plain",
    "batcher.evaluate", "hh.level"); a *shape key* is the signature the
    underlying program specializes on. `record_dispatch(site, key)`
    returns True exactly once per (site, key) — the compile — and
    False on every re-dispatch (the cache hit), matching jax's own
    executable cache for shape-bucketed callers.
    """

    def __init__(self, registry=None):
        self._lock = threading.Lock()
        self._registry = registry
        # (site, shape) -> [compiles, hits]
        self._shapes: Dict[Tuple[str, str], list] = {}
        # site -> list of compile latencies (ms), bounded
        self._compile_ms: Dict[str, list] = {}
        self._max_latencies = 256

    def bind_registry(self, registry) -> None:
        """Mirror future events into `registry` (duck-typed:
        `counter`/`gauge`/`histogram(name, labels=...)`)."""
        with self._lock:
            self._registry = registry

    # -- recording ----------------------------------------------------------

    def record_dispatch(
        self,
        site: str,
        key: str,
        compile_ms: Optional[float] = None,
    ) -> bool:
        """Count one dispatch of `site` at shape `key`. Returns True if
        this is the first sighting (a compile), else False (a hit).
        `compile_ms` attributes a measured first-call latency; pass it
        only when the call was actually timed."""
        with self._lock:
            entry = self._shapes.get((site, key))
            fresh = entry is None
            if fresh:
                self._shapes[(site, key)] = [1, 0]
            else:
                entry[1] += 1
            registry = self._registry
        if registry is not None:
            try:
                if fresh:
                    registry.counter(
                        "device.compiles", labels={"site": site}
                    ).inc()
                    registry.gauge(
                        "device.distinct_shapes", labels={"site": site}
                    ).inc()
                else:
                    registry.counter(
                        "device.dispatch_hits", labels={"site": site}
                    ).inc()
            except Exception:  # pragma: no cover - telemetry never raises
                pass
        if fresh and compile_ms is not None:
            self.record_compile_ms(site, compile_ms)
        return fresh

    def record_compile_ms(self, site: str, compile_ms: float) -> None:
        with self._lock:
            lat = self._compile_ms.setdefault(site, [])
            lat.append(float(compile_ms))
            del lat[: -self._max_latencies]
            registry = self._registry
        if registry is not None:
            try:
                registry.histogram(
                    "device.compile_ms", labels={"site": site}
                ).observe(float(compile_ms))
            except Exception:  # pragma: no cover
                pass

    @contextlib.contextmanager
    def dispatch(self, site: str, key: str):
        """Bracket one dispatch: times the call, and attributes the
        wall time as compile latency iff the shape is new (first call
        through a jit entry point includes trace+compile). A caller that
        times the same call in a span sets the yielded handle's `timing`
        (a `tracing.SpanTiming` closed inside this bracket): that one
        timing is then used.

        Chaos site `device.dispatch.<site>`: armed with action="oom"
        it raises `SimulatedResourceExhausted` at dispatch, standing
        in for the XLA allocator failing this program — the hook
        `pir/server.py`'s runtime tier demotion is tested against.
        """
        failpoints.fire(f"device.dispatch.{site}")
        handle = _Dispatch()
        t0 = time.perf_counter_ns()
        try:
            yield handle
        finally:
            timing = handle.timing
            elapsed_ms = (
                timing.ms if timing is not None and timing.end_ns is not None
                else (time.perf_counter_ns() - t0) / 1e6
            )
            # Record after the call so the compile histogram sees the
            # real first-call latency, not a guess made on entry.
            with self._lock:
                seen = (site, key) in self._shapes
            self.record_dispatch(
                site, key, compile_ms=None if seen else elapsed_ms
            )

    def track(self, site: str, fn: Callable, key_fn: Callable) -> Callable:
        """Wrap callable `fn` so each call records a dispatch under
        `site` with shape key `key_fn(*args, **kwargs)`."""

        def wrapper(*args, **kwargs):
            with self.dispatch(site, key_fn(*args, **kwargs)):
                return fn(*args, **kwargs)

        wrapper.__name__ = getattr(fn, "__name__", site)
        return wrapper

    # -- reading ------------------------------------------------------------

    def seen(self, site: str, key: str) -> bool:
        """Whether (site, key) has dispatched before — i.e. whether the
        NEXT dispatch at this shape hits an existing executable. Callers
        attribute a request's device step to the `compile` vs.
        `device_compute` phase with this, before entering `dispatch()`
        (which registers the shape on exit)."""
        with self._lock:
            return (site, key) in self._shapes

    def compiles(self, site: Optional[str] = None) -> int:
        with self._lock:
            return sum(
                v[0] for (s, _), v in self._shapes.items()
                if site is None or s == site
            )

    def hits(self, site: Optional[str] = None) -> int:
        with self._lock:
            return sum(
                v[1] for (s, _), v in self._shapes.items()
                if site is None or s == site
            )

    def export(self) -> dict:
        """Per-site summary for /statusz: shapes, compiles, hits,
        hit ratio, compile-latency percentiles."""
        with self._lock:
            shapes = {k: list(v) for k, v in self._shapes.items()}
            latencies = {s: list(v) for s, v in self._compile_ms.items()}
        sites: Dict[str, dict] = {}
        for (site, key), (compiles, hits) in sorted(shapes.items()):
            site_entry = sites.setdefault(
                site,
                {"shapes": {}, "compiles": 0, "hits": 0},
            )
            site_entry["shapes"][key] = {"compiles": compiles, "hits": hits}
            site_entry["compiles"] += compiles
            site_entry["hits"] += hits
        for site, entry in sites.items():
            total = entry["compiles"] + entry["hits"]
            entry["hit_ratio"] = (
                round(entry["hits"] / total, 4) if total else None
            )
            lat = sorted(latencies.get(site, ()))
            if lat:
                entry["compile_ms"] = {
                    "count": len(lat),
                    "p50": round(lat[len(lat) // 2], 3),
                    "max": round(lat[-1], 3),
                }
        return {"sites": sites, "total_compiles": self.compiles()}

    def reset(self) -> None:
        with self._lock:
            self._shapes.clear()
            self._compile_ms.clear()


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------


def _live_bytes() -> Tuple[int, str]:
    """(live bytes, source) for the default device. TPU backends answer
    `memory_stats()["bytes_in_use"]`; CPU returns None there, so fall
    back to summing `jax.live_arrays()`. Returns (0, "unavailable")
    when JAX itself is absent or uninitialized — telemetry must never
    be the thing that breaks a process."""
    try:
        import jax
    except Exception:  # pragma: no cover - jax is baked into the image
        return 0, "unavailable"
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if stats and "bytes_in_use" in stats:
        return int(stats["bytes_in_use"]), "memory_stats"
    try:
        return (
            sum(int(a.size) * a.dtype.itemsize for a in jax.live_arrays()),
            "live_arrays",
        )
    except Exception:
        return 0, "unavailable"


def device_memory_bytes() -> Optional[int]:
    """Total memory of the default device (`memory_stats()`'s
    `bytes_limit`), or None when the backend does not report one (CPU,
    uninitialized JAX). The capacity model seeds its byte budgets from
    this when no explicit budget or env override is given."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return None


class HbmAccountant:
    """Live-bytes watermarks with per-phase attribution.

    Phases name what the process is doing when memory peaks —
    `db_staging` (host->device database transfer), `selection`
    (materialized/streaming selection tensors during a request),
    `cut_state_cache` (heavy-hitters resume state). Within one phase
    occurrence the watermark is monotone non-decreasing; re-entering
    the phase resets it, so the gauge always describes the most recent
    occurrence.
    """

    def __init__(self, registry=None, sampler: Optional[Callable] = None):
        self._lock = threading.Lock()
        self._registry = registry
        self._sampler = sampler or _live_bytes
        self._watermarks: Dict[str, int] = {}
        self._current_phase: Optional[str] = None
        self._last_bytes = 0
        self._last_source = "unsampled"
        self._samples = 0

    def bind_registry(self, registry) -> None:
        with self._lock:
            self._registry = registry

    @contextlib.contextmanager
    def phase(self, name: str):
        """Bracket a phase occurrence: resets `name`'s watermark, takes
        an entry and an exit sample, and attributes any `sample()` call
        inside the bracket to `name`. Phases do not nest — the
        innermost bracket wins, and the outer phase resumes on exit."""
        with self._lock:
            prev = self._current_phase
            self._current_phase = name
            self._watermarks[name] = 0
        self.sample()
        try:
            yield
        finally:
            self.sample()
            with self._lock:
                self._current_phase = prev

    def sample(self) -> int:
        """Take one live-bytes sample, raising the current phase's
        watermark (and the unattributed `process` watermark)."""
        value, source = self._sampler()
        value = int(value)
        with self._lock:
            self._samples += 1
            self._last_bytes = value
            self._last_source = source
            phase = self._current_phase or "process"
            self._watermarks[phase] = max(
                value, self._watermarks.get(phase, 0)
            )
            registry = self._registry
            watermark = self._watermarks[phase]
        if registry is not None:
            try:
                registry.gauge("device.hbm_live_bytes").set(value)
                registry.gauge(
                    "device.hbm_watermark_bytes", labels={"phase": phase}
                ).set(watermark)
                registry.counter("device.hbm_samples").inc()
            except Exception:  # pragma: no cover
                pass
        return value

    def watermark(self, phase: str) -> int:
        with self._lock:
            return self._watermarks.get(phase, 0)

    def export(self) -> dict:
        with self._lock:
            return {
                "live_bytes": self._last_bytes,
                "source": self._last_source,
                "samples": self._samples,
                "current_phase": self._current_phase,
                "watermark_bytes": dict(sorted(self._watermarks.items())),
            }

    def reset(self) -> None:
        with self._lock:
            self._watermarks.clear()
            self._current_phase = None
            self._last_bytes = 0
            self._last_source = "unsampled"
            self._samples = 0


# ---------------------------------------------------------------------------
# Host<->device transfer accounting
# ---------------------------------------------------------------------------


def _tree_nbytes(x) -> int:
    """Total byte size of a value or pytree of array-likes. Leaves
    without `.nbytes` (or `.size`/`.dtype.itemsize`) count as 0 — the
    ledger measures traffic, it never raises."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(x)
    except Exception:  # pragma: no cover - jax is baked into the image
        leaves = [x]
    total = 0
    for leaf in leaves:
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None:
            size = getattr(leaf, "size", None)
            itemsize = getattr(
                getattr(leaf, "dtype", None), "itemsize", None
            )
            nbytes = (
                size * itemsize
                if size is not None and itemsize is not None
                else 0
            )
        total += int(nbytes)
    return total


class TransferLedger:
    """Per-phase host<->device transfer counts and bytes.

    ROADMAP item 3 ("kill host<->device round-trips on the hot path")
    needs a denominator: how many copies, how many bytes, and in which
    phase of the request they happen. Call sites route their transfers
    through the ledger's wrappers —

    * `device_put(x, sharding=None, phase=...)` — one counted h2d copy
      of a value or pytree (`jax.device_put`, sharding passed through);
    * `to_host(x, phase=...)` — one counted d2h readback
      (`np.asarray`);
    * `block_until_ready(x, phase=...)` — one counted host<->device
      sync round trip (no payload, pure latency);

    or report out-of-band with `record_h2d`/`record_d2h`/`record_sync`.
    Phases name the staging site (`db_staging`, `key_staging`,
    `result_readback`), so `/statusz` shows exactly which table row the
    latency program must drive to zero. With `enabled=False` the
    wrappers degrade to bare passthroughs (no lock, no counters) — the
    zero-overhead escape hatch.

    Syncs are counted distinctly from copies on purpose: a pipelined
    staging path issues many async copies but drains them with ONE
    final sync, so `syncs << h2d_copies` is the ledger-visible
    signature that transfers overlapped host work instead of each
    paying its own round trip. `record_overlap(ms, phase)` accumulates
    the companion `overlapped_ms` — host-side milliseconds spent doing
    useful work while copies were already in flight, i.e. transfer
    latency *hidden* behind the pipeline rather than exposed serially.
    """

    def __init__(self, registry=None, enabled: bool = True):
        self._lock = threading.Lock()
        self._registry = registry
        self.enabled = enabled
        # phase -> {h2d_copies, h2d_bytes, d2h_copies, d2h_bytes,
        #           syncs, overlapped_ms}
        self._phases: Dict[str, Dict[str, float]] = {}

    def bind_registry(self, registry) -> None:
        with self._lock:
            self._registry = registry

    # -- recording ----------------------------------------------------------

    @staticmethod
    def _new_entry() -> Dict[str, float]:
        return {"h2d_copies": 0, "h2d_bytes": 0, "d2h_copies": 0,
                "d2h_bytes": 0, "syncs": 0, "sync_wait_ms": 0.0,
                "overlapped_ms": 0.0}

    def _record(self, phase: str, field: str, copies: int,
                nbytes: int = 0) -> None:
        if not self.enabled:
            return
        with self._lock:
            entry = self._phases.setdefault(phase, self._new_entry())
            if field == "sync":
                entry["syncs"] += copies
            else:
                entry[f"{field}_copies"] += copies
                entry[f"{field}_bytes"] += nbytes
            registry = self._registry
        if registry is not None:
            try:
                labels = {"phase": phase}
                if field == "sync":
                    registry.counter(
                        "device.sync_waits", labels=labels
                    ).inc(copies)
                else:
                    registry.counter(
                        f"device.{field}_copies", labels=labels
                    ).inc(copies)
                    registry.counter(
                        f"device.{field}_bytes", labels=labels
                    ).inc(nbytes)
            except Exception:  # pragma: no cover - telemetry never raises
                pass

    def record_h2d(self, nbytes: int, phase: str, copies: int = 1) -> None:
        self._record(phase, "h2d", copies, int(nbytes))

    def record_d2h(self, nbytes: int, phase: str, copies: int = 1) -> None:
        self._record(phase, "d2h", copies, int(nbytes))

    def record_sync(self, phase: str, wait_ms: float = 0.0) -> None:
        """One host<->device sync round trip; `wait_ms` is the measured
        host wall time the sync blocked (the *exposed* half of the
        phase's transfer time — see `record_overlap` for the hidden
        half)."""
        self._record(phase, "sync", 1)
        wait_ms = float(wait_ms)
        if not self.enabled or wait_ms <= 0.0:
            return
        with self._lock:
            entry = self._phases.setdefault(phase, self._new_entry())
            entry["sync_wait_ms"] += wait_ms
            registry = self._registry
        if registry is not None:
            try:
                registry.counter(
                    "device.sync_wait_ms", labels={"phase": phase}
                ).inc(wait_ms)
            except Exception:  # pragma: no cover - telemetry never raises
                pass

    def record_overlap(self, ms: float, phase: str) -> None:
        """Credit `ms` of host work performed while transfers for
        `phase` were in flight (the hidden half of the phase's
        transfer time; the exposed half is whatever the final sync
        still waits)."""
        ms = float(ms)
        if not self.enabled or ms <= 0.0:
            return
        with self._lock:
            entry = self._phases.setdefault(phase, self._new_entry())
            entry["overlapped_ms"] += ms
            registry = self._registry
        if registry is not None:
            try:
                registry.counter(
                    "device.overlapped_ms", labels={"phase": phase}
                ).inc(ms)
            except Exception:  # pragma: no cover - telemetry never raises
                pass

    # -- counted wrappers ---------------------------------------------------

    def device_put(self, x, sharding=None, *, phase: str = "unattributed"):
        """Counted `jax.device_put` (one h2d copy of the whole value /
        pytree; `sharding` is any jax.device_put target)."""
        import jax

        out = (
            jax.device_put(x, sharding)
            if sharding is not None
            else jax.device_put(x)
        )
        if self.enabled:
            self.record_h2d(_tree_nbytes(x), phase)
        return out

    def to_host(self, x, *, phase: str = "unattributed"):
        """Counted device->host readback (`np.asarray`)."""
        import numpy as np

        out = np.asarray(x)
        if self.enabled:
            self.record_d2h(out.nbytes, phase)
        return out

    def block_until_ready(self, x, *, phase: str = "unattributed"):
        """Counted `jax.block_until_ready` (one sync round trip; the
        blocked wall time lands in the phase's `sync_wait_ms`)."""
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(x)
        if self.enabled:
            self.record_sync(
                phase, wait_ms=(time.perf_counter() - t0) * 1e3
            )
        return out

    # -- reading ------------------------------------------------------------

    def copies(self, phase: Optional[str] = None) -> int:
        """h2d copy count (one phase, or all phases)."""
        with self._lock:
            return sum(
                e["h2d_copies"] for p, e in self._phases.items()
                if phase is None or p == phase
            )

    def bytes_h2d(self, phase: Optional[str] = None) -> int:
        with self._lock:
            return sum(
                e["h2d_bytes"] for p, e in self._phases.items()
                if phase is None or p == phase
            )

    def syncs(self, phase: Optional[str] = None) -> int:
        """`block_until_ready` round trips (one phase, or all)."""
        with self._lock:
            return sum(
                e["syncs"] for p, e in self._phases.items()
                if phase is None or p == phase
            )

    def overlapped_ms(self, phase: Optional[str] = None) -> float:
        """Milliseconds of transfer time hidden behind host work."""
        with self._lock:
            return sum(
                e["overlapped_ms"] for p, e in self._phases.items()
                if phase is None or p == phase
            )

    def sync_wait_ms(self, phase: Optional[str] = None) -> float:
        """Milliseconds spent blocked in sync round trips (the exposed
        half of the transfer time)."""
        with self._lock:
            return sum(
                e.get("sync_wait_ms", 0.0)
                for p, e in self._phases.items()
                if phase is None or p == phase
            )

    def export(self) -> dict:
        with self._lock:
            phases = {p: dict(e) for p, e in sorted(self._phases.items())}
        totals = self._new_entry()
        for entry in phases.values():
            for k in totals:
                # Old-format entries (pre-overlap pickles/tests) may
                # lack the newer keys; treat absent as zero.
                totals[k] += entry.get(k, 0)
        for ms_key in ("overlapped_ms", "sync_wait_ms"):
            totals[ms_key] = round(totals[ms_key], 3)
            for entry in phases.values():
                if ms_key in entry:
                    entry[ms_key] = round(entry[ms_key], 3)
        return {"enabled": self.enabled, "totals": totals,
                "phases": phases}

    def reset(self) -> None:
        with self._lock:
            self._phases.clear()


# ---------------------------------------------------------------------------
# jax.monitoring bridge
# ---------------------------------------------------------------------------

_MONITORING_LOCK = threading.Lock()
_MONITORING_INSTALLED = False


def install_jax_monitoring_listener(tracker: "CompileTracker") -> bool:
    """Fold JAX's own compile-event durations into `tracker`, where the
    running jax exposes `jax.monitoring` (events like
    `/jax/core/compile/backend_compile_time_sec`). Installs one
    process-wide listener on first call; later calls (or a tracker
    swap via `set_default_telemetry`) retarget it instead of stacking
    listeners. Returns True when the listener is live."""
    global _MONITORING_INSTALLED
    with _MONITORING_LOCK:
        # The listener closes over the default telemetry indirection,
        # not `tracker` itself, so retargeting is free.
        if _MONITORING_INSTALLED:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        register = getattr(
            monitoring, "register_event_duration_secs_listener", None
        )
        if register is None:
            return False

        def _on_event(event: str, duration_secs: float, **_kwargs) -> None:
            if "compile" not in event:
                return
            site = "jax." + event.strip("/").split("/")[-1]
            telemetry = default_telemetry()
            telemetry.compile_tracker.record_compile_ms(
                site, duration_secs * 1e3
            )

        try:
            register(_on_event)
        except Exception:  # pragma: no cover - listener is best-effort
            return False
        _MONITORING_INSTALLED = True
        return True


# ---------------------------------------------------------------------------
# Process-default telemetry
# ---------------------------------------------------------------------------


class DeviceTelemetry:
    """One process's device telemetry: a compile tracker, an HBM
    accountant, and a transfer ledger, bound to at most one metrics
    registry."""

    def __init__(self, registry=None):
        self.compile_tracker = CompileTracker(registry)
        self.hbm = HbmAccountant(registry)
        self.transfers = TransferLedger(registry)
        # Optional persistent-compilation-cache info: a dict, or a
        # zero-arg callable returning one (re-counted per scrape).
        # Installed by the serving startup (`install_compile_cache`),
        # so /statusz's compile table can show cold-vs-warm counts.
        self._compile_cache_info = None

    def bind_registry(self, registry) -> None:
        self.compile_tracker.bind_registry(registry)
        self.hbm.bind_registry(registry)
        self.transfers.bind_registry(registry)

    def set_compile_cache_info(self, provider) -> None:
        self._compile_cache_info = provider

    def export(self) -> dict:
        out = {
            "compile": self.compile_tracker.export(),
            "hbm": self.hbm.export(),
            "transfers": self.transfers.export(),
        }
        info = self._compile_cache_info
        if callable(info):
            try:
                info = info()
            except Exception:  # noqa: BLE001 - telemetry must not raise
                info = None
        if info:
            out["compile_cache"] = info
        return out

    def reset(self) -> None:
        self.compile_tracker.reset()
        self.hbm.reset()
        self.transfers.reset()


_DEFAULT = DeviceTelemetry()


def default_telemetry() -> DeviceTelemetry:
    """The process-wide telemetry instance the hot paths report into.
    Layers above (serving, pir, heavy_hitters) call this instead of
    holding references, so a test swap via `set_default_telemetry`
    reaches every dispatch site immediately."""
    return _DEFAULT


def set_default_telemetry(telemetry: DeviceTelemetry) -> DeviceTelemetry:
    global _DEFAULT
    _DEFAULT = telemetry
    return telemetry
