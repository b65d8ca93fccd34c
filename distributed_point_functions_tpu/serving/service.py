"""Leader/Helper/Plain serving sessions: the production runtime roles.

`pir/server.py` implements the protocol roles with injected transport
and crypto callbacks; these sessions wrap them into deployable objects:

* every plain evaluation (a plain-role request, the Leader's own share,
  the Helper's decrypted request) routes through one `DynamicBatcher`
  per session via the server's `set_plain_handler` hook, so concurrent
  requests share device steps and jit cache entries;
* requests carry per-request **deadlines** (default
  `ServingConfig.request_timeout_ms`) enforced both in the batcher
  queue and on the submitting thread;
* the Leader's Helper leg gets a per-attempt **timeout** and bounded
  **exponential-backoff retry**; exhausted retries raise
  `HelperUnavailable`, or — when the operator opts in with
  `allow_degraded` — degrade to the Leader's own
  `handle_plain_request` share so the session keeps answering (the
  response is flagged in metrics; a client that sees degraded service
  must fall back to plain single-server queries to read real records);
* a **circuit breaker** (`robustness/breaker.py`) fronts the helper
  leg: after `breaker_failure_threshold` consecutive leg failures it
  opens and requests fast-fail to `HelperUnavailable` in well under a
  millisecond instead of paying the timeout+backoff ladder each; after
  `breaker_reset_ms` one half-open probe request runs the real leg,
  and its success closes the breaker AND exits degraded mode — the
  next responses are full two-share answers again. Breaker state is a
  gauge (`leader.breaker_state`: 0 closed / 1 half-open / 2 open —
  point an SLO `gauge_max` objective at it for a burn signal), an
  export on the session (`breaker_export()`, the /statusz row), and
  counters (`leader.breaker_opens`, `leader.breaker_fast_fails`,
  `leader.degraded_exits`);
* a `MetricsRegistry` per session (injectable, so co-located sessions
  can share one) records queue/batch/retry/latency counters, exported
  with `session.metrics.export()`;
* every request roots an observability **trace** (`observability/
  tracing.py`): wire decode/encode, queue wait, batch assembly, and
  device compute land as spans, the finished trace lands in the flight
  recorder (`/tracez` on an `AdminServer`). On the Leader, the trace id
  rides to the Helper inside a versioned envelope
  (`observability/propagation.py`) and the Helper's server-side spans
  come back in the reply, so helper-leg RTT decomposes into network
  vs. Helper-reported compute. Old-version peers interop: a Helper fed
  a bare proto answers a bare proto, and a Leader whose enveloped
  request faults a v0 Helper downgrades that transport to bare proto
  (counted in `leader.wire_downgrades`) and retries within its
  existing retry budget.

Sessions speak either library `messages.PirRequest` objects
(`handle_request`) or the framed proto wire format (`handle_wire`,
pluggable straight into `transport.FramedTcpServer`).
"""

from __future__ import annotations

import contextvars
import dataclasses
import os
import time
from typing import Optional

import threading

from .. import serialization
from ..capacity.admission import AdmissionController, TenantPolicy
from ..capacity.brownout import BrownoutController
from ..capacity.recalibrate import CapacityAccuracy, default_recalibrator
from ..observability import costmodel as costmodel_mod
from ..observability import events as events_mod
from ..observability import critical_path, propagation, tracing
from ..observability import phases as phases_mod
from ..observability.utilization import default_utilization_tracker
from ..observability.device import (
    default_telemetry,
    install_jax_monitoring_listener,
)
from ..pir import messages
from ..pir.database import DenseDpfPirDatabase
from ..pir.server import DenseDpfPirServer, clear_tier_floor, set_tier_floor
from ..robustness import failpoints
from ..robustness.breaker import CircuitBreaker
from .batcher import DeadlineExceeded, DynamicBatcher, Overloaded
from .metrics import MetricsRegistry
from .snapshots import SnapshotMismatch
from .transport import Transport, TransportError, TransportTimeout

__all__ = [
    "ServingConfig",
    "HelperUnavailable",
    "SnapshotMismatch",
    "PlainSession",
    "LeaderSession",
    "HelperSession",
    "DeadlineExceeded",
    "Overloaded",
    "TenantPolicy",
]


class HelperUnavailable(RuntimeError):
    """The Helper leg failed every attempt (timeouts and/or refusals)."""


@dataclasses.dataclass
class ServingConfig:
    """Operator knobs for a serving session.

    `request_timeout_ms=None` disables deadlines (a cold first request
    compiles jit programs and may legitimately take minutes on CPU).
    `helper_retries` counts retries *after* the first attempt; backoff
    doubles from `helper_backoff_ms` up to `helper_backoff_max_ms`.
    `allow_degraded=True` opts into Leader-share-only responses when the
    Helper is permanently down (see module docstring for the privacy
    and correctness contract).

    The breaker fields shape the Leader's helper-leg circuit breaker:
    it opens after `breaker_failure_threshold` consecutive failed legs
    (each exhausted retry ladder counts its attempts individually) and
    admits one half-open probe per `breaker_reset_ms` window.
    `breaker_enabled=False` restores the PR 2 behavior (every request
    pays the full ladder).

    `admission_enabled=True` replaces the batcher's request-count
    bound with cost-aware admission (`capacity/admission.py`): doomed
    and over-quota requests shed at submit with a `retry_after_s`
    hint, per-tenant quotas/weights via `session.set_tenant()`, and
    weighted-fair dequeue. `admission_queue_budget_ms` is the queued
    estimated-device-ms the controller will hold before shedding.

    The helper retry *budget* bounds the retry:success ratio so
    retries cannot amplify an overload: each successful leg earns
    `helper_retry_budget_ratio` retry tokens (capped at
    `helper_retry_budget_min`, which is also the starting balance) and
    each retry spends one; an empty budget skips the remaining ladder
    and raises `HelperUnavailable` immediately (counted in
    `leader.retries_budget_exhausted`). The PR 7 breaker handles a
    *dead* Helper; the budget handles a *slow* one.
    """

    max_batch_size: int = 64
    max_wait_ms: float = 2.0
    max_queue: int = 256
    request_timeout_ms: Optional[float] = None
    helper_timeout_ms: Optional[float] = 30_000.0
    helper_retries: int = 2
    helper_backoff_ms: float = 10.0
    helper_backoff_max_ms: float = 250.0
    allow_degraded: bool = False
    batching: bool = True
    breaker_enabled: bool = True
    breaker_failure_threshold: int = 5
    breaker_reset_ms: float = 1000.0
    admission_enabled: bool = False
    admission_queue_budget_ms: float = 250.0
    helper_retry_budget_ratio: float = 0.1
    helper_retry_budget_min: float = 10.0
    # False pins the Leader's envelope probe at v1: no Helper phase
    # digest, no skew estimate, no critical-path decomposition — the
    # knob the digest-piggyback overhead benchmark flips.
    helper_digest: bool = True
    # How many times the Leader re-runs a request whose Helper answer
    # came from a different database generation (typed
    # SnapshotMismatch, never a cross-generation XOR). Retries
    # converge because the Leader's own pending flip applies at the
    # next batch boundary; the window is the coordinator's bounded
    # Helper-first/Leader-last flip gap.
    snapshot_retries: int = 3
    # Batcher pipeline depth: 2 (default) lets the worker dispatch
    # bucket N while a completion thread fans out bucket N-1 (see
    # serving/batcher.py); 1 restores strictly serial
    # dispatch-then-complete batches.
    pipeline_depth: int = 2
    # Device-utilization accounting (observability/utilization.py):
    # the batcher worker/completion threads and the Leader's helper
    # leg report busy/idle intervals with typed bubble causes into the
    # process-wide tracker (read on /utilz). False detaches it — the
    # knob the utilization_overhead benchmark flips.
    utilization: bool = True


# The deadline travels from handle_request into the server's plain
# handler (called synchronously, possibly from inside the Leader's
# while_waiting callback on the same thread) without threading it
# through the reference-mirroring server signatures.
_DEADLINE: contextvars.ContextVar = contextvars.ContextVar(
    "serving_deadline", default=None
)
# The requesting tenant rides the same way: set at handle_request,
# read where the plain handler submits to the batcher.
_TENANT: contextvars.ContextVar = contextvars.ContextVar(
    "serving_tenant", default="default"
)
# The snapshot generation the most recent batched evaluation on this
# context bound to (set by _batched_plain_handler from the batcher's
# batch-boundary stamp). The Helper's handle_wire echoes it in the v3
# reply; the Leader's _send_to_helper compares it against the Helper's
# echo. Reset to None at each entry point so a stale value from a
# previous request on the same thread can never bleed into the check.
_EVAL_GENERATION: contextvars.ContextVar = contextvars.ContextVar(
    "serving_eval_generation", default=None
)


# ---------------------------------------------------------------------------
# Persistent JAX compilation cache (process-wide)
# ---------------------------------------------------------------------------

# The fixed fallback location: the cache key includes the path, so a
# directory that moves between runs never hits.
REPO_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)
_compile_cache_state: Optional[dict] = None
_compile_cache_lock = threading.Lock()


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:
        return 0


def install_compile_cache() -> dict:
    """The one compile-cache rule for sessions, `chip_smoke.py` and
    `bench.py`: when `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
    itself and nothing is set here; otherwise the cache goes to the
    fixed `<repo>/.jax_cache`, persisting every program (serving's jit
    shapes are few and bucketed, and the cold first request is what the
    cache exists to kill). Idempotent and process-wide; returns the
    state dict — cache dir, entries present at startup, and entries
    persisted by this process — which is also pushed into the device
    telemetry so `/statusz`'s compile table shows it."""
    global _compile_cache_state
    import jax

    with _compile_cache_lock:
        if _compile_cache_state is not None:
            return _compile_cache_state
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
        if not path:
            path = REPO_COMPILE_CACHE_DIR
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
            for knob, value in (
                ("jax_persistent_cache_min_compile_time_secs", 0.0),
                ("jax_persistent_cache_min_entry_size_bytes", -1),
            ):
                jax.config.update(knob, value)
        state = {"dir": path, "warm_entries_at_start": _cache_entries(path)}
        _compile_cache_state = state

    def _info() -> dict:
        current = _cache_entries(path)
        return dict(
            state,
            entries=current,
            persisted_this_process=max(
                0, current - state["warm_entries_at_start"]
            ),
        )

    default_telemetry().set_compile_cache_info(_info)
    return state


class _Session:
    """Shared session mechanics: batcher wiring, deadlines, wire codec."""

    def __init__(
        self,
        server: DenseDpfPirServer,
        config: Optional[ServingConfig],
        metrics: Optional[MetricsRegistry],
        name: str,
    ):
        self._server = server
        self._config = config if config is not None else ServingConfig()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._name = name
        # Opt-in persistent compilation cache first: it must be wired
        # before the session's first jit dispatch to serve warm
        # programs.
        install_compile_cache()
        # Device telemetry rides the session's registry: compile events
        # and HBM watermarks from the dispatch sites below show up on
        # this session's /metrics and /statusz. The jax.monitoring
        # bridge is one process-wide listener (idempotent install).
        default_telemetry().bind_registry(self.metrics)
        install_jax_monitoring_listener(default_telemetry().compile_tracker)
        phases_mod.default_phase_recorder().bind_registry(self.metrics)
        # Cost-model accuracy: the process-wide ledger mirrors residual
        # histograms + the drift gauge into this session's registry,
        # and the shared recalibrator closes the loop on the default
        # capacity model's prices. `capacity_accuracy` is the read
        # model /capacityz and the /statusz section render.
        ledger = costmodel_mod.default_cost_ledger()
        ledger.bind_registry(self.metrics)
        self.capacity_accuracy = CapacityAccuracy(
            ledger=ledger, recalibrator=default_recalibrator()
        )
        # Snapshot rotation (serving/snapshots.py): a SnapshotManager
        # wires itself in via attach_snapshots at construction.
        self.snapshots = None
        self.admission: Optional[AdmissionController] = None
        if self._config.admission_enabled:
            self.admission = AdmissionController(
                queue_budget_ms=self._config.admission_queue_budget_ms,
                metrics=self.metrics,
                name=f"{name}.admission",
            )
        self._batcher: Optional[DynamicBatcher] = None
        if self._config.batching:
            self._batcher = DynamicBatcher(
                self._evaluate_keys,
                max_batch_size=self._config.max_batch_size,
                max_wait_ms=self._config.max_wait_ms,
                max_queue=self._config.max_queue,
                metrics=self.metrics,
                name=f"{name}.batcher",
                admission=self.admission,
                pipeline_depth=self._config.pipeline_depth,
            )
            server.set_plain_handler(self._batched_plain_handler)
        # Device-utilization accounting: the batcher threads (and the
        # helper leg below) report busy/idle intervals into the
        # process-wide tracker; gauges/bubble histograms mirror into
        # this session's registry. config.utilization=False detaches.
        # Replica-scoped event routing: None means the process-global
        # journal (single-replica deployments, unchanged); the fleet
        # telemetry plane points this at a scoped journal so breaker
        # transitions, degraded-mode flips, and generation-skew lines
        # carry replica identity when N replicas share one process.
        self._session_journal = None
        self._util = None
        if self._config.utilization:
            self._util = default_utilization_tracker()
            self._util.bind_registry(self.metrics)
            if self._batcher is not None:
                self._batcher.set_utilization(self._util)
        # Mesh wiring: a 2-D-mesh server tells the batcher its key-axis
        # granularity (buckets pad to it, so batches land
        # pre-partitioned) and the capacity model its shape (admission
        # and brownout then price per-shard bytes and per-mesh q/s
        # without any changes of their own).
        is_2d = getattr(server, "_mesh_is_2d", None)
        if callable(is_2d) and is_2d():
            multiple = int(server.batch_key_multiple())
            if multiple > 1 and self._batcher is not None:
                self._batcher.set_key_multiple(multiple)
            mesh = server._mesh
            axes = tuple(mesh.axis_names)
            from ..capacity.model import default_capacity_model

            default_capacity_model().configure_mesh(
                int(mesh.shape[axes[0]]), int(mesh.shape[axes[1]])
            )

    @property
    def server(self) -> DenseDpfPirServer:
        return self._server

    @property
    def config(self) -> ServingConfig:
        return self._config

    @property
    def batcher(self) -> Optional[DynamicBatcher]:
        return self._batcher

    def attach_snapshots(self, manager):
        """Wire a `SnapshotManager` into the session: generation flips
        then land only at this session's batch boundaries, and the
        wire entry points carry/check the generation field."""
        self.snapshots = manager
        if self._batcher is not None:
            self._batcher.set_generation_source(manager)
        return manager

    def set_journal(self, journal):
        """Route this session's own events through `journal` (a
        replica-scoped `EventJournal`); None restores the process
        journal."""
        self._session_journal = journal
        return journal

    def _emit(self, kind, message, severity="info", **fields):
        journal = (
            self._session_journal
            if getattr(self, "_session_journal", None) is not None
            else events_mod.default_journal()
        )
        try:
            journal.emit(kind, message, severity=severity, **fields)
        except Exception:  # noqa: BLE001 - journaling never breaks serving
            pass

    def attach_workload(self, observatory, key_fn=None):
        """Wire a `WorkloadObservatory` onto this session's hot path:
        every `handle_request` observes batch size, tenant, and
        deadline headroom. Key indices stay out by default — DPF keys
        hide them from this server (the protocol's point) — unless the
        caller supplies `key_fn(request) -> indices`, legitimate only
        where indices are public (plain/trusted deployments, load
        generators). Returns `observatory` for chaining."""
        self._workload = observatory
        self._workload_key_fn = key_fn
        return observatory

    def _observe_workload(self, request, deadline, tenant, now) -> None:
        observatory = getattr(self, "_workload", None)
        if observatory is None:
            return
        try:
            plain = getattr(request, "plain_request", None)
            num_keys = len(plain.dpf_keys) if plain is not None else 1
            key_fn = getattr(self, "_workload_key_fn", None)
            indices = key_fn(request) if key_fn is not None else None
            observatory.observe(
                num_keys=num_keys,
                tenant=tenant,
                key_indices=indices,
                deadline_s=(
                    max(0.0, deadline - now)
                    if deadline is not None
                    else None
                ),
                now=now,
            )
        except Exception:  # noqa: BLE001 - observation never breaks serving
            pass

    def set_utilization(self, tracker):
        """Swap this session's utilization tracker — the fleet telemetry
        plane rebinds each replica's sessions to a replica-scoped
        tracker so N replicas in one process stop reporting busy/idle
        into the shared process-global one. Mirrors the construction
        wiring: gauges bind into this session's registry and the batcher
        threads report into the new tracker from the next interval on."""
        self._util = tracker
        if tracker is not None:
            tracker.bind_registry(self.metrics)
            if self._batcher is not None:
                self._batcher.set_utilization(tracker)
        return tracker

    # -- QoS / brownout -----------------------------------------------------

    def set_tenant(self, tenant: str, policy: TenantPolicy) -> None:
        """Register a tenant's QoS contract (requires
        `admission_enabled`)."""
        if self.admission is None:
            raise RuntimeError(
                "set_tenant requires ServingConfig.admission_enabled"
            )
        self.admission.set_tenant(tenant, policy)

    def attach_brownout(
        self,
        brownout: BrownoutController,
        batch_cap: int = 8,
        cheap_tier: str = "streaming",
    ) -> BrownoutController:
        """Wire the ladder's steps to this session's knobs: admission
        priority floors (steps 1 and 4, when admission is enabled),
        the batcher's batch cap (step 2), and the process-wide PIR
        tier floor (step 3). Returns `brownout` for chaining."""
        if self.admission is not None:
            adm = self.admission
            brownout.add_step_action(
                "shed_low_priority",
                lambda: adm.set_min_priority(1),
                lambda: adm.set_min_priority(0),
            )
            # Reverts land in reverse engage order, so critical_only
            # reverting returns the floor to 1 (shed_low_priority is
            # still engaged at that point).
            brownout.add_step_action(
                "critical_only",
                lambda: adm.set_min_priority(2),
                lambda: adm.set_min_priority(1),
            )
        if self._batcher is not None:
            batcher = self._batcher
            brownout.add_step_action(
                "cap_batches",
                lambda: batcher.set_batch_cap(batch_cap),
                lambda: batcher.set_batch_cap(None),
            )
        brownout.add_step_action(
            "force_cheap_tier",
            lambda: set_tier_floor(cheap_tier),
            clear_tier_floor,
        )
        return brownout

    # -- batching -----------------------------------------------------------

    def _evaluate_keys(self, keys):
        """The batcher's evaluation function: one real device step for
        the whole coalesced key batch."""
        response = self._server.handle_plain_request(
            messages.PirRequest(
                plain_request=messages.PlainRequest(dpf_keys=keys)
            )
        )
        return response.dpf_pir_response.masked_response

    def _batched_plain_handler(self, request):
        out, generation = self._batcher.submit_ex(
            request.plain_request.dpf_keys,
            deadline=_DEADLINE.get(),
            tenant=_TENANT.get(),
        )
        if generation is not None:
            # Published for the enclosing entry point (the Helper's
            # echo / the Leader's own-share generation): deliberately
            # un-scoped — the reader is up-stack on this same context
            # and each entry point resets it to None first.
            _EVAL_GENERATION.set(generation)
        return messages.PirResponse(
            dpf_pir_response=messages.DpfPirResponse(
                masked_response=list(out)
            )
        )

    # -- request entry points -----------------------------------------------

    def _default_deadline(self) -> Optional[float]:
        if self._config.request_timeout_ms is None:
            return None
        return time.monotonic() + self._config.request_timeout_ms / 1e3

    def handle_request(
        self,
        request: "messages.PirRequest",
        deadline: Optional[float] = None,
        tenant: str = "default",
    ) -> "messages.PirResponse":
        """Serve one request; `deadline` is absolute `time.monotonic()`
        seconds (defaults from `request_timeout_ms`); `tenant` keys the
        admission QoS policy when enabled."""
        if deadline is None:
            deadline = self._default_deadline()
        self._observe_workload(request, deadline, tenant, time.monotonic())
        token = _DEADLINE.set(deadline)
        tenant_token = _TENANT.set(tenant)
        try:
            with tracing.trace_request(
                f"{self._name}.request", role=self._name
            ):
                # One timing for the request: its start and end feed
                # both the waterfall's end-to-end total (when this call
                # roots the record, not a wire entry point around it)
                # and `<role>.request_ms`.
                outer = phases_mod.current_request()
                start_ns = time.perf_counter_ns()
                with phases_mod.default_phase_recorder().request(
                    self._name, start_ns=start_ns
                ) as phases:
                    try:
                        return self._server.handle_request(request)
                    finally:
                        end_ns = time.perf_counter_ns()
                        if phases is not None and phases is not outer:
                            phases.end_ns = end_ns
                        self.metrics.histogram(
                            f"{self._name}.request_ms"
                        ).observe((end_ns - start_ns) / 1e6)
        finally:
            _TENANT.reset(tenant_token)
            _DEADLINE.reset(token)

    def handle_wire(self, data: bytes) -> bytes:
        """Framed proto entry point (plugs into `FramedTcpServer`).

        An incoming trace-context envelope (a new-version Leader's
        helper leg) is unwrapped here: the inner proto serves under the
        propagated trace id and the reply wraps back with this side's
        stage spans. A bare proto (old-version peer, or a client) is
        served and answered bare, unchanged.

        A shed request (`Overloaded`) from an *enveloped* peer answers
        with a typed kind-3 error envelope carrying the `retry_after_s`
        hint; a bare-proto peer sees the exception propagate to the
        transport exactly as before (old peers could not parse the
        envelope anyway).

        The reply always uses the *request's* envelope version, so a v1
        Leader never sees v2 fields. A v2 request gets the critical-path
        digest piggybacked on the reply: this side's phase waterfall
        plus the perf_counter-domain receive/send timestamps the Leader
        needs for NTP-style skew estimation. A v3 request gets the
        snapshot generation this side's share was evaluated against
        echoed in the reply meta — the Leader's cross-generation check
        depends on that echo being the *evaluated* generation, not
        whatever is serving by reply time.
        """
        from ..protos import private_information_retrieval_pb2 as pir_pb2

        recv_ms = time.perf_counter() * 1e3
        trace_id, inner, req_version, req_generation = (
            propagation.try_decode_request_ext(data)
        )
        resp_version = min(req_version, propagation.PROPAGATION_VERSION)
        # Fresh per request: a stale generation from a previous request
        # on this thread must never be echoed as this one's.
        _EVAL_GENERATION.set(None)
        t0 = time.perf_counter()
        with tracing.trace_request(
            f"{self._name}.request",
            trace_id=trace_id,
            fresh=trace_id is not None,
            role=self._name,
        ) as trace:
            # fresh at the RPC boundary for the same reason as the
            # trace: an in-process transport runs this on the Leader's
            # thread, and the Helper's phases must not merge into the
            # Leader's record.
            with phases_mod.default_phase_recorder().request(
                self._name, fresh=trace_id is not None
            ):
                with phases_mod.phase("respond", span="decode"):
                    proto = pir_pb2.PirRequest.FromString(inner)
                    request = serialization.pir_request_from_proto(
                        self._server.dpf, proto
                    )
                try:
                    response = self.handle_request(request)
                except Overloaded as e:
                    if trace_id is None:
                        raise
                    self.metrics.counter(
                        f"{self._name}.wire_overloads"
                    ).inc()
                    return propagation.encode_error(
                        "overloaded",
                        message=str(e),
                        retry_after_s=getattr(e, "retry_after_s", 0.0),
                        trace_id=trace.trace_id,
                    )
                with phases_mod.phase("respond", span="encode"):
                    out = serialization.pir_response_to_proto(
                        response
                    ).SerializeToString()
            if trace_id is None:
                return out
            # The generation this request's share actually evaluated
            # against (stamped at the batch boundary); falls back to
            # the serving generation for unbatched sessions.
            served_generation = _EVAL_GENERATION.get()
            if served_generation is None and self.snapshots is not None:
                served_generation = self.snapshots.serving_generation()
            if (
                req_generation is not None
                and served_generation is not None
                and req_generation != served_generation
            ):
                # The peer believed a different generation was current
                # when it sent. Harmless here — the Leader's echo check
                # is the enforcement point — but worth a (coalesced)
                # line on the timeline while the rotation window is
                # open.
                self._emit(
                    "snapshot.mismatch",
                    f"request bound generation {req_generation}, "
                    f"evaluated against {served_generation}",
                    severity="warning",
                    party=self._name,
                    request_generation=req_generation,
                    served_generation=served_generation,
                    coalesce_key=(
                        f"snapshot.skew:{self._name}:"
                        f"{req_generation}:{served_generation}"
                    ),
                    coalesce_s=1.0,
                )
            # The phases context has closed: trace.attrs["phases"] is
            # this request's final waterfall (the v2 digest).
            return propagation.encode_response(
                out,
                trace.trace_id,
                server_ms=(time.perf_counter() - t0) * 1e3,
                spans=trace.span_list(),
                version=resp_version,
                phases=trace.attrs.get("phases"),
                recv_ms=recv_ms,
                send_ms=time.perf_counter() * 1e3,
                generation=served_generation,
            )

    def close(self) -> None:
        if self._batcher is not None:
            self._server.set_plain_handler(None)
            self._batcher.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class PlainSession(_Session):
    """Single-server (trusted) serving: plain requests, batched.

    `server=` swaps in a pre-built plain-role server (the sparse
    sessions in `serving/sparse.py` reuse every session mechanic this
    way); the default builds a dense server from `database`."""

    def __init__(
        self,
        database: Optional[DenseDpfPirDatabase] = None,
        config: Optional[ServingConfig] = None,
        metrics: Optional[MetricsRegistry] = None,
        mesh=None,
        server=None,
    ):
        if server is None:
            server = DenseDpfPirServer.create_plain(database, mesh=mesh)
        super().__init__(server, config, metrics, "plain")


class HelperSession(_Session):
    """The Helper role: decrypts its leg, evaluates (batched), masks."""

    def __init__(
        self,
        database: Optional[DenseDpfPirDatabase] = None,
        decrypter=None,
        config: Optional[ServingConfig] = None,
        metrics: Optional[MetricsRegistry] = None,
        mesh=None,
        server=None,
    ):
        if server is None:
            server = DenseDpfPirServer.create_helper(
                database, decrypter, mesh=mesh
            )
        super().__init__(server, config, metrics, "helper")


class LeaderSession(_Session):
    """The Leader role: forwards the encrypted Helper leg over an
    injected `Transport` with timeout/retry/backoff, computes its own
    share while waiting, and XOR-combines the masked responses.

    `server=` swaps in a pre-built leader-role server; build it around
    this session's `self._send_to_helper` bound method (subclasses set
    `self._transport` first, then construct the server — see
    `serving/sparse.py:SparseLeaderSession`)."""

    def __init__(
        self,
        database: Optional[DenseDpfPirDatabase] = None,
        helper_transport: Optional[Transport] = None,
        config: Optional[ServingConfig] = None,
        metrics: Optional[MetricsRegistry] = None,
        mesh=None,
        server=None,
    ):
        self._transport = helper_transport
        if server is None:
            server = DenseDpfPirServer.create_leader(
                database, self._send_to_helper, mesh=mesh
            )
        super().__init__(server, config, metrics, "leader")
        m = self.metrics
        self._c_retries = m.counter("leader.helper_retries")
        self._c_timeouts = m.counter("leader.helper_timeouts")
        self._c_failures = m.counter("leader.helper_failures")
        self._c_degraded = m.counter("leader.degraded_responses")
        self._c_downgrades = m.counter("leader.wire_downgrades")
        self._c_mismatches = m.counter("leader.snapshot_mismatches")
        self._c_snapshot_retries = m.counter("leader.snapshot_retries")
        # None = envelope support unknown (probe with an envelope);
        # False = peer rejected it once (bare proto from then on);
        # True = peer answered an envelope.
        self._peer_envelope: Optional[bool] = None
        # Envelope version ladder: probe at v3 (generation handshake +
        # critical-path digest), step one version down per non-timeout
        # fault — v3 -> v2 (losing only the generation echo; checking
        # goes disabled-but-journaled) -> v1 (losing the digest) ->
        # bare proto. Each step is sticky, retry-neutral, and counted
        # once in leader.wire_downgrades, so an old Helper costs
        # exactly (3 - its version) probes.
        self._peer_wire_version = (
            propagation.PROPAGATION_VERSION
            if self._config.helper_digest else 1
        )
        # Critical-path analysis rides the phase recorder's close hook;
        # install is idempotent and binds critical.* to this registry.
        critical_path.install(registry=m)
        # Degraded mode is now *state*, not just a per-response counter:
        # entered when a request falls back to its Leader-only share,
        # exited the moment the breaker's half-open probe closes it.
        self._degraded = False
        self._g_degraded = m.gauge("leader.degraded_mode")
        self._c_degraded_exits = m.counter("leader.degraded_exits")
        self._g_breaker = m.gauge("leader.breaker_state")
        self._c_breaker_opens = m.counter("leader.breaker_opens")
        self._c_fast_fails = m.counter("leader.breaker_fast_fails")
        self._c_helper_overloaded = m.counter("leader.helper_overloaded")
        # Retry budget: bounds the retry:success ratio so an overloaded
        # (slow-but-alive) Helper is not hammered with amplified load.
        self._c_budget_exhausted = m.counter(
            "leader.retries_budget_exhausted"
        )
        self._retry_lock = threading.Lock()
        self._retry_tokens = float(self._config.helper_retry_budget_min)
        self._g_retry_tokens = m.gauge("leader.retry_budget_tokens")
        self._g_retry_tokens.set(self._retry_tokens)
        self._breaker: Optional[CircuitBreaker] = None
        if self._config.breaker_enabled:
            self._breaker = CircuitBreaker(
                failure_threshold=self._config.breaker_failure_threshold,
                reset_timeout_ms=self._config.breaker_reset_ms,
                name="leader.helper",
            )
            self._breaker.on_transition(self._on_breaker_transition)

    @property
    def degraded(self) -> bool:
        """Whether the session is currently answering Leader-share-only
        responses (recoverable: a successful half-open probe exits)."""
        return self._degraded

    @property
    def breaker(self) -> Optional[CircuitBreaker]:
        return self._breaker

    def breaker_export(self) -> Optional[dict]:
        """The /statusz row for this session's helper-leg breaker."""
        if self._breaker is None:
            return None
        out = self._breaker.export()
        out["degraded_mode"] = self._degraded
        return out

    def _on_breaker_transition(self, old: str, new: str) -> None:
        self._g_breaker.set(float(self._breaker.state_code()))
        self._emit(
            "breaker.transition",
            f"helper-leg breaker {old} -> {new}",
            severity="error" if new == "open" else "info",
            old=old,
            new=new,
        )
        if new == "open":
            self._c_breaker_opens.inc()
        if new == "closed" and self._degraded:
            # The half-open probe proved the Helper healthy again:
            # degraded mode ends here, not at process restart.
            self._degraded = False
            self._g_degraded.set(0.0)
            self._c_degraded_exits.inc()

    # -- helper leg ---------------------------------------------------------

    def _retry_budget_take(self) -> bool:
        """Spend one retry token; False means the budget is exhausted
        and the ladder must stop retrying."""
        with self._retry_lock:
            if self._retry_tokens >= 1.0:
                self._retry_tokens -= 1.0
                self._g_retry_tokens.set(round(self._retry_tokens, 3))
                return True
            return False

    def _retry_budget_earn(self) -> None:
        """A successful leg earns back `helper_retry_budget_ratio`
        tokens, capped at the starting balance — the cap is what bounds
        the long-run retry:success ratio."""
        cfg = self._config
        with self._retry_lock:
            self._retry_tokens = min(
                float(cfg.helper_retry_budget_min),
                self._retry_tokens + cfg.helper_retry_budget_ratio,
            )
            self._g_retry_tokens.set(round(self._retry_tokens, 3))

    def _send_to_helper(self, helper_request, while_waiting):
        """`ForwardHelperRequestFn` with retry: serialize, round-trip
        with a per-attempt timeout, back off and retry on transport
        faults. `while_waiting` (the Leader's own share) runs exactly
        once, overlapped with the first successful send.

        The request goes out wrapped in a trace-context envelope until
        the peer proves it is old-version: a non-timeout fault on an
        envelope probe (an old Helper fails parsing the envelope and
        drops the connection) steps the version ladder — v2 to v1
        (losing only the critical-path digest), then v1 to bare proto —
        before the normal retry policy resumes. Timeouts do NOT
        downgrade — a slow Helper is not an old one.
        """
        # Fresh per leg: the own-share evaluation below stamps the
        # generation it bound to; a stale stamp from a previous request
        # on this thread must never satisfy the echo check.
        _EVAL_GENERATION.set(None)
        breaker = self._breaker
        if breaker is not None and not breaker.allow():
            # Open breaker: fail in microseconds — no serialization, no
            # connect, no backoff. The caller's degraded path (or the
            # client's retry policy) takes over.
            self._c_fast_fails.inc()
            raise HelperUnavailable(
                "helper circuit breaker is open (fast-fail)"
            )
        wire = serialization.pir_request_to_proto(
            self._server.dpf, helper_request
        ).SerializeToString()
        cfg = self._config
        called = [False]
        # The own-share window (perf_counter ms): the skew estimator
        # subtracts whatever part of it ran serially inside the
        # round-trip bracket, so own-share compute is never booked as
        # wire time (the in-process transport runs it inline).
        share_window = [None]
        # The generation the own share bound to, captured the moment
        # the share returns. It must NOT be read from _EVAL_GENERATION
        # after the round-trip: an in-process Helper runs handle_wire
        # on this same thread/context and would overwrite it with the
        # HELPER's generation — turning the mismatch check into
        # helper-vs-helper, which can never fire.
        own_gen_box = [None]

        def leader_share_once():
            if not called[0]:
                called[0] = True
                s0 = time.perf_counter()
                try:
                    with tracing.span("leader_own_share"):
                        while_waiting()
                finally:
                    share_window[0] = (s0 * 1e3,
                                       time.perf_counter() * 1e3)
                    own_gen_box[0] = _EVAL_GENERATION.get()

        timeout = (
            None if cfg.helper_timeout_ms is None
            else cfg.helper_timeout_ms / 1e3
        )
        backoff = cfg.helper_backoff_ms / 1e3
        trace = tracing.current_trace()
        last: Optional[Exception] = None
        attempt = 0
        while attempt <= cfg.helper_retries:
            enveloped = self._peer_envelope is not False
            payload = (
                propagation.encode_request(
                    trace.trace_id if trace is not None
                    else tracing.new_trace_id(),
                    wire,
                    version=self._peer_wire_version,
                    # Advisory: the serving generation at send time
                    # (the own share has not evaluated yet — it runs
                    # overlapped with this round-trip). The Helper
                    # journals skew against it; the authoritative
                    # check below compares echo vs. own-share binding.
                    generation=(
                        self.snapshots.serving_generation()
                        if self.snapshots is not None else None
                    ),
                )
                if enveloped
                else wire
            )
            try:
                # Chaos site: an injected fault here exercises the
                # retry ladder and the breaker exactly like a helper
                # timeout would.
                failpoints.fire("service.helper_leg", error=TransportTimeout)
                # One timing of the round trip: the rtt, the
                # `leader.helper_leg_ms` histogram and the logged span
                # (the parent of an in-process Helper's request).
                with tracing.span("helper_leg", record=False) as leg:
                    data = self._transport.roundtrip(
                        payload, timeout=timeout,
                        on_sent=leader_share_once,
                    )
                t0 = leg.start_ns / 1e9
                rtt_ms = leg.ms
                self.metrics.histogram("leader.helper_leg_ms").observe(rtt_ms)
                if breaker is not None:
                    breaker.record_success()
                self._retry_budget_earn()
                break
            except Exception as e:  # noqa: BLE001 - triaged below
                is_transport = isinstance(e, TransportError)
                if (
                    enveloped
                    and self._peer_envelope is None
                    and not isinstance(e, TransportTimeout)
                ):
                    # Probe fault: plausibly an old peer choking on the
                    # envelope. Step ONE version down the ladder — v3
                    # to v2 (losing the generation echo; checking goes
                    # disabled-but-journaled), v2 to v1 (losing the
                    # digest), then v1 to bare proto — and re-send
                    # immediately. No step consumes a retry attempt
                    # (each is sticky, so the ladder runs at most
                    # PROPAGATION_VERSION times per transport) or feeds
                    # the breaker: a version mismatch is not a dead
                    # Helper.
                    if self._peer_wire_version > 1:
                        self._peer_wire_version -= 1
                    else:
                        self._peer_envelope = False
                    self._c_downgrades.inc()
                    last = e
                    continue
                if not is_transport:
                    raise
                if breaker is not None:
                    breaker.record_failure()
                last = e
                if isinstance(e, TransportTimeout):
                    self._c_timeouts.inc()
                if attempt >= cfg.helper_retries:
                    self._c_failures.inc()
                    raise HelperUnavailable(
                        f"helper leg failed after {attempt + 1} "
                        f"attempt(s): {e}"
                    ) from e
                if not self._retry_budget_take():
                    # The fleet-level retry:success ratio is spent:
                    # retrying now would amplify the very overload
                    # that is making the Helper slow. Fail fast and
                    # let the client's backoff spread the load.
                    self._c_budget_exhausted.inc()
                    self._c_failures.inc()
                    raise HelperUnavailable(
                        f"helper retry budget exhausted after "
                        f"{attempt + 1} attempt(s): {e}"
                    ) from e
                self._c_retries.inc()
                time.sleep(min(backoff, cfg.helper_backoff_max_ms / 1e3))
                backoff *= 2
                attempt += 1
        else:
            self._c_failures.inc()
            raise HelperUnavailable(
                f"helper leg failed after {attempt} attempt(s): {last}"
            ) from last
        # A misbehaving-but-fast helper could answer before the share ran.
        leader_share_once()
        # Out-of-band attribution: the helper leg's RTT overlaps the
        # Leader's own-share compute (by design), so the waterfall's
        # helper_rtt phase can exceed end-to-end minus device_compute.
        phases_mod.record("helper_rtt", rtt_ms)
        # Utilization: only the RTT tail NOT hidden behind the
        # own-share compute is a real barrier — the Leader sat idle
        # from the share's end to the round-trip's return.
        if getattr(self, "_util", None) is not None:
            exposed_ms = (
                rtt_ms if share_window[0] is None
                else max(0.0, (t0 * 1e3 + rtt_ms) - share_window[0][1])
            )
            try:
                self._util.record_idle(
                    "helper_rtt", exposed_ms / 1e3, thread="leader"
                )
            except Exception:  # noqa: BLE001 - accounting never breaks serving
                pass
        try:
            meta, inner = (
                propagation.try_decode_response(data)
                if enveloped
                else (None, data)
            )
        except propagation.WireErrorResponse as e:
            # A typed refusal is a live, envelope-speaking peer (the
            # breaker already recorded the round-trip as a success) —
            # surface it as Overloaded with the peer's backoff hint
            # rather than burning retries against a shedding Helper.
            self._peer_envelope = True
            if e.error_type == "overloaded":
                self._c_helper_overloaded.inc()
                raise Overloaded(
                    f"helper shed the request: {e}",
                    retry_after_s=e.retry_after_s,
                    reason="helper_overloaded",
                ) from e
            raise
        if enveloped:
            self._peer_envelope = meta is not None
        if self.snapshots is not None:
            # The generation handshake. own_generation is what this
            # Leader's share actually evaluated against (stamped at
            # the batch boundary by _batched_plain_handler, captured
            # at share return — see own_gen_box above);
            # helper_generation is the Helper's echo of the same for
            # its share. Disagreement means the XOR would be
            # well-formed garbage — refuse typed, never combine.
            own_generation = own_gen_box[0]
            helper_generation = (
                meta.get("generation") if meta is not None else None
            )
            if helper_generation is None:
                if own_generation is not None:
                    # Pre-v3 peer (or a Helper without rotation
                    # machinery): checking is disabled for this peer,
                    # journaled so the gap is visible.
                    self.snapshots.note_unchecked(
                        self._peer_wire_version
                        if self._peer_envelope else 0
                    )
            elif (
                own_generation is not None
                and own_generation != helper_generation
            ):
                self._c_mismatches.inc()
                self.snapshots.record_mismatch(
                    own_generation,
                    helper_generation,
                    trace_id=(
                        trace.trace_id if trace is not None else None
                    ),
                )
                raise SnapshotMismatch(own_generation, helper_generation)
        if meta is not None:
            # Decompose the helper leg: the Helper reports its own
            # server time, the rest of the RTT is the network (plus
            # framing) — and the Helper's stage spans graft on under a
            # `helper.` prefix.
            remote_ms = float(meta.get("server_ms", 0.0))
            network_ms = max(0.0, rtt_ms - remote_ms)
            self.metrics.histogram("leader.helper_remote_ms").observe(
                remote_ms
            )
            self.metrics.histogram("leader.helper_network_ms").observe(
                network_ms
            )
            # v2 digest: NTP-style skew estimate from this exchange's
            # four timestamps, then the helper_net / helper_queue /
            # helper_compute split. The own-share window is subtracted
            # from the exchange rtt where it overlapped the bracket.
            skew = None
            decomp = None
            t0_ms, t3_ms = t0 * 1e3, t0 * 1e3 + rtt_ms
            if meta.get("recv_ms") is not None and (
                meta.get("send_ms") is not None
            ):
                win = share_window[0]
                overlap_ms = (
                    max(0.0, min(win[1], t3_ms) - max(win[0], t0_ms))
                    if win is not None else 0.0
                )
                skew = critical_path.estimate_skew(
                    t0_ms, t3_ms,
                    float(meta["recv_ms"]), float(meta["send_ms"]),
                    overlap_ms=overlap_ms,
                )
                decomp = critical_path.decompose_helper_leg(
                    skew, meta.get("phases")
                )
                if decomp is not None:
                    phases_mod.record(
                        "helper_net", decomp["helper_net_ms"]
                    )
                    phases_mod.record(
                        "helper_queue", decomp["helper_queue_ms"]
                    )
                    phases_mod.record(
                        "helper_compute", decomp["helper_compute_ms"]
                    )
                req = phases_mod.current_request()
                if req is not None:
                    req.set_meta("helper_leg", {
                        "rtt_ms": rtt_ms,
                        "own_ms": (
                            win[1] - win[0] if win is not None else 0.0
                        ),
                        "skew": skew.as_dict(),
                        "decomp": decomp,
                        "helper_phases": meta.get("phases") or {},
                    })
            if trace is not None:
                extra = {}
                if skew is not None:
                    extra["offset_ms_est"] = round(skew.offset_ms, 3)
                    extra["offset_uncertainty_ms"] = round(
                        skew.uncertainty_ms, 3
                    )
                trace.add_span(
                    "helper_leg", rtt_ms, remote_ms=round(remote_ms, 3),
                    network_ms=round(network_ms, 3), **extra,
                )
                # With a skew estimate, remote spans land at their
                # corrected position on THIS trace's timeline: the
                # Helper's recv_ms maps into the Leader clock via the
                # offset, then rebases against the trace start.
                base_offset_ms = None
                if skew is not None:
                    trace_start_ms = (
                        time.perf_counter() * 1e3 - trace.elapsed_ms()
                    )
                    base_offset_ms = (
                        float(meta["recv_ms"]) - skew.offset_ms
                        - trace_start_ms
                    )
                trace.add_remote_spans(
                    meta.get("spans", []), prefix="helper.",
                    base_offset_ms=base_offset_ms,
                )
                trace.add_span("helper_network", network_ms)
        elif trace is not None:
            trace.add_span("helper_leg", rtt_ms)
        from ..protos import private_information_retrieval_pb2 as pir_pb2

        with tracing.span("decode"):
            return serialization.pir_response_from_proto(
                pir_pb2.PirResponse.FromString(inner)
            )

    # -- degradation --------------------------------------------------------

    def handle_request(self, request, deadline=None):
        if deadline is None:
            deadline = self._default_deadline()
        retries = max(0, self._config.snapshot_retries)
        attempt = 0
        while True:
            try:
                return self._handle_request_once(request, deadline)
            except SnapshotMismatch:
                # Typed cross-generation refusal from the handshake:
                # retry the WHOLE request — the own share re-evaluates
                # (binding to the post-flip generation once the
                # pending flip lands at a batch boundary) and the
                # Helper leg re-runs. Bounded: the coordinator flips
                # Helper-first/Leader-last, so the window closes as
                # soon as this party's flip applies.
                if attempt >= retries:
                    raise
                attempt += 1
                self._c_snapshot_retries.inc()
                # A breath per attempt: the flip this retry is waiting
                # on applies on the batcher worker, not this thread.
                time.sleep(0.002 * attempt)

    def _handle_request_once(self, request, deadline):
        try:
            return super().handle_request(request, deadline)
        except HelperUnavailable:
            if not (
                self._config.allow_degraded
                and request.leader_request is not None
            ):
                raise
            # Operator-sanctioned degraded mode: answer with the
            # Leader's own share only. The client cannot unmask a real
            # record from this (the Helper's share is missing) — it is a
            # liveness signal telling clients to fall back to plain
            # queries — but the session stays up and keeps its batcher,
            # metrics, and deadlines exercised. The mode is recoverable:
            # the breaker's half-open probe closing it flips
            # `self._degraded` back off (see _on_breaker_transition).
            self._c_degraded.inc()
            if not self._degraded:
                self._degraded = True
                self._g_degraded.set(1.0)
                self._emit(
                    "service.degraded",
                    "helper unavailable; serving leader-share-only",
                    severity="error",
                )
            token = _DEADLINE.set(deadline)
            try:
                return self._server._dispatch_plain(
                    messages.PirRequest(
                        plain_request=request.leader_request.plain_request
                    )
                )
            finally:
                _DEADLINE.reset(token)

    def close(self):
        super().close()
        self._transport.close()
