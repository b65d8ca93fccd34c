"""End-to-end request observability: tracing, device telemetry, SLOs,
events, debug bundles, exposition, utilization, timeseries, admin
surface.

Sixteen pieces, importable from any layer above `utils/` (the layer DAG
is serving -> observability -> utils; this package never imports pir/,
ops/, or serving/ — `device`/`slo` reach JAX lazily and only for
device facts):

* `tracing` — per-request spans with trace ids, a bounded flight
  recorder retaining the slowest/errored traces, process-wide stage
  aggregates, and runtime counters for layers below serving.
* `phases` — per-request latency attribution into a canonical phase
  catalog (queue -> batch -> h2d_transfer -> compile -> dispatch ->
  device_compute -> helper_rtt -> respond), aggregated per role into
  the `/statusz` waterfall.
* `device` — compile-event tracker (one compile per new dispatch
  shape, cache hits, compile-latency histograms, a `jax.monitoring`
  bridge), the HBM accountant (live-bytes watermarks with per-phase
  attribution), and the host<->device `TransferLedger` (per-phase
  copy counts and bytes — the round-trip counter ROADMAP item 3
  drives to zero).
* `slo` — declarative latency/throughput/compile-budget objectives
  graded continuously against the metrics registry; hard breaches
  degrade `/healthz` to 503 and fire burn listeners.
* `autoprofile` — SLO-triggered profiling: one bounded xprof capture
  per latency-burn transition, with cooldown and a capture ring on
  `/statusz`.
* `events` — the unified event journal: one process-global bounded
  ring of typed operational transitions (breaker flips, brownout
  steps, SLO burns, tier demotions, sweep resumes, sheds, failpoint
  arming, probe failures) surfaced at `/eventz` and on `/statusz`.
* `bundle` — incident debug bundles: on an SLO hard breach, breaker
  open, or probe bit-identity failure, snapshot statusz/metrics/
  traces/journal/probe history atomically into one directory, with
  cooldown and bounded retention (`/debugz`).
* `propagation` — the versioned envelope that carries a trace id on
  the Leader->Helper wire and the Helper's stage timings back
  (old-version peers interop by detection); v2 piggybacks the
  Helper's per-request phase digest and recv/send timestamps.
* `critical_path` — the cross-party merge: NTP-style clock-skew
  estimation from each exchange, helper_rtt decomposed into
  helper_net / helper_queue / helper_compute, the two-party DAG
  walked to mark the critical leg, aggregated into the `/criticalz`
  per-(phase, party) profile.
* `costmodel` — the cost-model accuracy ledger: joins admission-time
  capacity prices (device-ms, peak bytes) with measured truth at every
  terminal batch outcome and folded sweep level into per-(workload,
  tier, shape-bucket) residual reservoirs, detects sustained drift
  (journal event + SLO gauge), and feeds the guarded recalibration
  loop in `capacity/recalibrate.py`.
* `utilization` — the device-utilization timeline: busy/idle interval
  ledger over the batcher worker/completion threads, every idle bubble
  attributed to a typed cause (empty_queue, batch_wait, pipeline_full,
  staging_sync, helper_rtt, snapshot_flip, admission_shed), per-window
  duty-cycle % and `device_feed_efficiency`, per-shard busy ratios
  with a `util.straggler` journal watch (`/utilz`).
* `timeseries` — the in-process flight-data recorder: a bounded
  multi-resolution ring TSDB, a jittered sampler snapshotting selected
  registry series plus the utilization windows, and a rate-of-change
  anomaly watch journaling `util.anomaly` (`/timeseriesz`, debug
  bundles).
* `workload` — per-tenant hot-path traffic characterization in bounded
  memory: count-min sketch + top-K hot keys with an online Zipf fit,
  EWMA arrival rate and CV² burstiness, deadline/batch histograms, and
  periodicity detection over the TSDB's coarse tier (`/workloadz`).
* `forecast` — the predictive capacity plane: dependency-free Holt
  forecasts over selected TSDB series with confidence bands and
  predicted time-to-breach against declared ceilings, journaled as
  coalesced `forecast.breach_predicted` events and gradable as a soft
  SLO objective (`/forecastz`).
* `exposition` — Prometheus text rendering of the metrics registry,
  including OpenMetrics-style exemplars linking buckets to traces.
* `admin` — the `/metrics` `/varz` `/healthz` `/statusz` `/tracez`
  `/eventz` `/probez` `/debugz` `/profilez` `/criticalz` `/capacityz`
  `/utilz` `/timeseriesz` `/workloadz` `/forecastz` operator HTTP
  endpoint.
"""

from .admin import AdminServer
from .autoprofile import AutoProfiler
from .bundle import BundleManager
from .events import (
    EventJournal,
    default_journal,
    emit,
    set_default_journal,
    watch_failpoints,
)
from .device import (
    CompileTracker,
    DeviceTelemetry,
    HbmAccountant,
    TransferLedger,
    default_telemetry,
    install_jax_monitoring_listener,
    set_default_telemetry,
    shape_key,
)
from .costmodel import (
    CostLedger,
    default_cost_ledger,
    drift_objective,
    set_default_cost_ledger,
    shape_bucket,
)
from .critical_path import (
    CriticalPathAnalyzer,
    SkewEstimate,
    decompose_helper_leg,
    default_analyzer,
    estimate_skew,
    set_default_analyzer,
)
from .phases import (
    PHASES,
    PhaseRecorder,
    RequestPhases,
    current_request,
    default_phase_recorder,
    set_default_phase_recorder,
)
from .exposition import parse_labeled_name, render_prometheus
from .forecast import Forecaster, SeriesForecast, holt_fit
from .slo import SloObjective, SloTracker
from .workload import (
    CountMinSketch,
    TopKTracker,
    WorkloadObservatory,
    detect_periodicity,
    fit_zipf_exponent,
)
from .timeseries import (
    AnomalyWatch,
    MetricsSampler,
    TimeSeriesStore,
    render_sparklines,
    sparkline,
)
from .utilization import (
    BUBBLE_CAUSES,
    UtilizationTracker,
    default_utilization_tracker,
    set_default_utilization_tracker,
)
from .propagation import (
    EnvelopeError,
    encode_request,
    encode_response,
    try_decode_request,
    try_decode_request_full,
    try_decode_response,
)
from .tracing import (
    CounterGroup,
    FlightRecorder,
    Trace,
    add_span,
    current_trace,
    default_recorder,
    new_trace_id,
    reset_stages,
    runtime_counters,
    set_default_recorder,
    span,
    stage_summary,
    trace_request,
)

__all__ = [
    "AdminServer",
    "AnomalyWatch",
    "AutoProfiler",
    "BUBBLE_CAUSES",
    "BundleManager",
    "CompileTracker",
    "CostLedger",
    "CountMinSketch",
    "CounterGroup",
    "CriticalPathAnalyzer",
    "DeviceTelemetry",
    "EnvelopeError",
    "EventJournal",
    "FlightRecorder",
    "Forecaster",
    "HbmAccountant",
    "MetricsSampler",
    "PHASES",
    "PhaseRecorder",
    "RequestPhases",
    "SeriesForecast",
    "SkewEstimate",
    "SloObjective",
    "SloTracker",
    "TimeSeriesStore",
    "TopKTracker",
    "Trace",
    "TransferLedger",
    "UtilizationTracker",
    "WorkloadObservatory",
    "add_span",
    "current_request",
    "current_trace",
    "decompose_helper_leg",
    "detect_periodicity",
    "default_analyzer",
    "default_cost_ledger",
    "default_journal",
    "default_phase_recorder",
    "default_recorder",
    "default_telemetry",
    "default_utilization_tracker",
    "drift_objective",
    "emit",
    "encode_request",
    "encode_response",
    "estimate_skew",
    "fit_zipf_exponent",
    "holt_fit",
    "install_jax_monitoring_listener",
    "new_trace_id",
    "parse_labeled_name",
    "render_prometheus",
    "render_sparklines",
    "reset_stages",
    "runtime_counters",
    "set_default_analyzer",
    "set_default_cost_ledger",
    "set_default_journal",
    "set_default_phase_recorder",
    "set_default_recorder",
    "set_default_telemetry",
    "set_default_utilization_tracker",
    "shape_bucket",
    "shape_key",
    "span",
    "sparkline",
    "stage_summary",
    "trace_request",
    "try_decode_request",
    "try_decode_request_full",
    "try_decode_response",
    "watch_failpoints",
]
