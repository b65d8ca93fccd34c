#!/usr/bin/env bash
# Presubmit pipeline — the TPU build's equivalent of the reference's
# .bazelci/presubmit.yml:15-34 (two-compiler matrix, benchmark-tagged
# targets excluded). Stages:
#   1. lint        — stdlib AST lint (tools/lint.py)
#   2. layers      — serving -> pir -> ops layer DAG + import-cycle
#                    check (tools/check_layers.py)
#   3. protos      — generated *_pb2.py match protos/*.proto
#   4. native      — C++ oracle kernels build (g++)
#   5. test-fast   — <5 min hermetic signal tier (incl. tiny-shape
#                    interpret cases of every serving Pallas kernel)
#   6. hh-smoke    — heavy-hitters sweep end to end (tiny domain,
#                    2 levels, in-process transport, plaintext check)
#   7. admin-smoke — operator telemetry endpoint: serve one traced
#                    request, then scrape /healthz, /metrics (Prometheus
#                    text with exemplars), /statusz (compile counts, HBM
#                    watermarks, SLO burn, phase waterfall, transfer
#                    ledger, auto-captured profiles) and /tracez off a
#                    live AdminServer, check a hard SLO breach degrades
#                    /healthz to 503, and check a synthetic latency-SLO
#                    burn produces exactly one auto-capture entry
#   8. critical-smoke — cross-party critical path over a real TCP
#                    Leader/Helper pair: the skew-corrected
#                    decomposition on /criticalz must account for the
#                    measured helper rtt (helper_net + helper_queue +
#                    helper_compute == exchange rtt exactly, within
#                    own-share overlap + stated uncertainty of the raw
#                    rtt), and the merged two-party timeline on
#                    /tracez must be monotone per party
#   9. chaos-smoke — one scripted fault schedule through the real
#                    stack: a permanently-failing helper leg must open
#                    the Leader's circuit breaker (fast-fail, /statusz
#                    row), and a heavy-hitters sweep killed mid-run
#                    must resume from its checkpoint to the plaintext
#                    answer
#  10. overload-smoke — synthetic burst against cost-aware admission:
#                    a tiny tenant quota must shed at admission with a
#                    typed RetryAfter hint (never reaching the batcher),
#                    and a breaching SLO signal must walk the brownout
#                    ladder to critical_only (visible on /statusz) and
#                    fully auto-revert when the signal clears
#  11. prober-smoke — blackbox-verification chaos drill: a `corrupt`
#                    failpoint armed on the helper-leg response wire
#                    (via DPF_TPU_FAILPOINTS, so the event journal
#                    shows the arming) must be flagged by the prober
#                    within 3 cycles, capture exactly one debug bundle
#                    (cooldown respected) whose journal tail correlates
#                    the timeline, degrade /healthz once the e2e probe
#                    goes stale, and fully recover (probez passing,
#                    /healthz 200) after the failpoint clears
#  12. capacity-accuracy-smoke — the cost-model accuracy loop closed
#                    on live traffic: a deliberately mispriced pir
#                    workload (DPF_TPU_COSTMODEL_MISPRICE) served
#                    through a real PlainSession must populate
#                    /capacityz with finite residuals, journal a
#                    capacity.drift event, burn the drift SLO gauge,
#                    apply a clamped (<= 2x) correction to subsequent
#                    admission prices with bit-identical responses,
#                    and fully revert under the recalibration kill
#                    switch
#  13. shard-smoke — pod-scale mesh serving end to end on 8 forced
#                    host devices: closed-loop traffic against one
#                    logical server spread over a 2-D (shard x key)
#                    mesh, one snapshot rotation mid-traffic, zero
#                    prober failures, no cross-generation reads, the
#                    per-shard staging visible in mesh_export, and the
#                    per-shard busy rows on /utilz
#  14. pipeline-smoke — the hot-path pipelining contract end to end:
#                    depth-2 batcher under closed-loop load, one delta
#                    rotation (prestage saves bytes), prober green
#                    through the flip, /statusz shows overlapped
#                    (hidden) transfer time
#  15. util-smoke  — the device-seconds ledger end to end: closed-loop
#                    traffic must put a nonzero duty cycle with a
#                    populated bubble breakdown (causes summing to the
#                    measured idle) on /utilz, an injected helper-leg
#                    delay failpoint must journal a util.anomaly via
#                    the rate-of-change watch, and a debug bundle
#                    captured after the stall must carry >= 60 s of
#                    flight-data history with the anomaly in its
#                    journal tail
#  16. sparse-smoke — key-value (cuckoo) PIR at serving parity:
#                    closed-loop sparse traffic through the batched
#                    session, one key-value write batch landing as a
#                    SnapshotManager delta rotation under load
#                    (prestage saves bytes), zero sparse-prober
#                    failures through the flip, and the golden absent
#                    key resolving to typed not-found throughout
#  17. forecast-smoke — the predictive capacity plane end to end:
#                    synthetic load ramped toward a deliberately
#                    lowered calibrated capacity must journal a
#                    forecast.breach_predicted BEFORE any hard SLO
#                    burn, /forecastz?format=json must carry a finite
#                    time-to-breach, the predictive governor must
#                    visibly tighten tenant quotas on /capacityz, and
#                    everything must revert exactly once the ramp
#                    recedes — with every served response bit-identical
#                    to the oracle throughout
#  18. perf-gate   — benchmarks/regression_gate.py --check-only against
#                    the committed history fixture (CPU-safe: judges
#                    records, runs no bench)
#  19. dryrun      — 8-virtual-device multichip compile+step
# Benchmarks are excluded exactly as the reference excludes
# `--test_tag_filters=-benchmark`. `FULL=1` appends the whole suite.
set -u -o pipefail
cd "$(dirname "$0")/.."
fail=0

stage() {
    echo "=== presubmit: $1 ==="
    shift
    "$@" || { echo "FAILED: $*"; fail=1; }
}

stage lint python tools/lint.py

stage layers python tools/check_layers.py

stage protoc-check bash -c '
    tmp=$(mktemp -d) &&
    protoc --python_out="$tmp" -Iprotos \
        protos/distributed_point_function.proto \
        protos/distributed_comparison_function.proto \
        protos/multiple_interval_containment.proto \
        protos/private_information_retrieval.proto \
        protos/hash_family_config.proto &&
    ok=0 &&
    for f in "$tmp"/*_pb2.py; do
        name=$(basename "$f")
        cmp -s "$f" "distributed_point_functions_tpu/protos/$name" \
            || { echo "stale generated proto: $name"; ok=1; }
    done; rm -rf "$tmp"; exit $ok'

stage native bash -c 'cd native && bash build.sh'

stage test-fast make -s test-fast

stage hh-smoke env JAX_PLATFORMS=cpu \
    python examples/heavy_hitters_demo.py --smoke

stage admin-smoke env JAX_PLATFORMS=cpu python -c '
import json, urllib.error, urllib.request
from distributed_point_functions_tpu import observability as obs
from distributed_point_functions_tpu.observability import phases as pm
from distributed_point_functions_tpu.observability.slo import (
    SloObjective, SloTracker,
)
from distributed_point_functions_tpu.serving.metrics import MetricsRegistry

reg = MetricsRegistry()
rec = obs.tracing.FlightRecorder()
dev = obs.DeviceTelemetry(registry=reg)
phases = obs.PhaseRecorder()
with obs.tracing.trace_request("smoke.request", recorder=rec):
    with obs.tracing.span("smoke.request", record=False) as timing:
        with phases.request("smoke"):
            with pm.phase("h2d_transfer"):
                dev.transfers.record_h2d(4096, "key_staging")
            pm.record("device_compute", 2.5)
            with obs.tracing.span("device_compute"):
                with dev.hbm.phase("db_staging"):
                    dev.hbm.sample()
    reg.histogram("smoke.request_ms").observe(timing.ms)
with dev.compile_tracker.dispatch("smoke.evaluate", "q64.b8192"):
    pass
with dev.compile_tracker.dispatch("smoke.evaluate", "q64.b8192"):
    pass
slo = SloTracker(
    [SloObjective(name="smoke_p99", kind="p99_ms_max",
                  metric="smoke.request_ms", threshold=1e-9)],
    registry=reg,
)
prof = obs.AutoProfiler(
    slo, capture_fn=lambda r: {"log_dir": "/tmp/smoke-capture"},
    async_capture=False,
)
slo.evaluate()  # synthetic latency-SLO burn -> one inline capture
slo.evaluate()  # continuing breach must NOT re-fire
assert len(prof.captures()) == 1, prof.export()
with obs.AdminServer(registry=reg, recorder=rec, device=dev,
                     slo=slo, phases=phases,
                     autoprofiler=prof) as admin:
    base = f"http://127.0.0.1:{admin.port}"
    text = urllib.request.urlopen(base + "/metrics").read().decode()
    assert "# TYPE dpf_smoke_request_ms histogram" in text, text
    assert "dpf_smoke_request_ms_bucket" in text, text
    assert "# {trace_id=" in text, text  # exemplar on a bucket line
    assert "dpf_device_compiles" in text, text
    statusz = urllib.request.urlopen(base + "/statusz").read().decode()
    for needle in ("smoke.evaluate", "q64.b8192", "db_staging",
                   "SLO burn", "smoke_p99",
                   "Phase waterfall", "h2d_transfer", "device_compute",
                   "transfers", "key_staging",
                   "Auto-captured profiles", "/tmp/smoke-capture"):
        assert needle in statusz, (needle, statusz)
    sz_json = json.load(
        urllib.request.urlopen(base + "/statusz?format=json")
    )
    assert sz_json["phases"]["smoke"]["requests"] == 1, sz_json["phases"]
    led = sz_json["device"]["transfers"]["phases"]["key_staging"]
    assert led["h2d_copies"] == 1 and led["h2d_bytes"] == 4096, led
    assert len(sz_json["profiles"]["captures"]) == 1, sz_json["profiles"]
    sz = json.load(urllib.request.urlopen(base + "/statusz?format=json"))
    site = sz["device"]["compile"]["sites"]["smoke.evaluate"]
    assert site["compiles"] == 1 and site["hits"] == 1, site
    tracez = json.load(urllib.request.urlopen(base + "/tracez"))
    assert tracez["recorded"] == 1 and tracez["slowest"], tracez
    spans = [s["name"] for s in tracez["slowest"][0]["spans"]]
    assert "device_compute" in spans, spans
    try:
        urllib.request.urlopen(base + "/healthz")
        raise AssertionError("breached SLO did not degrade /healthz")
    except urllib.error.HTTPError as e:
        assert e.code == 503, e.code
        body = e.read().decode()
        assert "slo breach: smoke_p99" in body, body
    reg.reset()  # breach clears -> next probe recovers
    assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"
assert len(prof.captures()) == 1, prof.export()  # still exactly one
print("admin-smoke: OK (/metrics incl. exemplars, /statusz incl. phase "
      "waterfall + transfer ledger + auto-captures, /tracez, /healthz "
      "incl. SLO degrade+recover, one capture per burn)")
'

stage critical-smoke env JAX_PLATFORMS=cpu python -c '
import json, urllib.request
import numpy as np
from distributed_point_functions_tpu.observability import AdminServer
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.serving import (
    FramedTcpServer, HelperSession, LeaderSession, ServingConfig,
    TcpTransport,
)
from distributed_point_functions_tpu.testing import encrypt_decrypt

rng = np.random.default_rng(7)
records = [bytes(rng.integers(0, 256, 16, dtype=np.uint8))
           for _ in range(64)]
builder = DenseDpfPirDatabase.Builder()
for r in records:
    builder.insert(r)
database = builder.build()
config = ServingConfig(max_batch_size=4, max_wait_ms=2.0)
helper = HelperSession(database, encrypt_decrypt.decrypt, config)
server = FramedTcpServer(
    helper.handle_wire, port=0, name="critical-helper"
).start()
transport = TcpTransport("localhost", server.port)
leader = LeaderSession(database, transport, config)
client = DenseDpfPirClient.create(64, encrypt_decrypt.encrypt)
try:
    with helper, leader:
        for idx in (3, 17, 41):
            request, state = client.create_request([idx])
            response = leader.handle_request(request)
            assert client.handle_response(response, state) == [
                records[idx]
            ], idx
finally:
    transport.close()
    server.stop()
with AdminServer(registry=leader.metrics) as admin:
    base = f"http://127.0.0.1:{admin.port}"
    crit = json.load(
        urllib.request.urlopen(base + "/criticalz?format=json")
    )
    assert crit["requests"] == 3, crit
    assert crit["skew_invalid"] == 0, crit
    last = crit["last"]["leader"]
    assert last["skew_valid"] is True, last
    total = (last["helper_net_ms"] + last["helper_queue_ms"]
             + last["helper_compute_ms"])
    # Identity: the split accounts for the exchange rtt exactly.
    assert abs(total - last["exchange_ms"]) < 1e-2, last
    # ... and for the raw measured rtt within the honest tolerance:
    # the own-share overlap that provably ran serially inside the
    # bracket (bounded by own_ms and, when the concurrency cap
    # engages, equal to 2x the stated uncertainty) plus codec slop.
    assert abs(total - last["rtt_ms"]) <= (
        last["own_ms"] + 2.0 * last["uncertainty_ms"] + 1.0
    ), last
    prof = crit["profile"]
    assert prof and all(
        "p99_ms" in cell
        for party in prof.values() for cell in party.values()
    ), prof
    sz = json.load(urllib.request.urlopen(base + "/statusz?format=json"))
    assert sz["critical"]["requests"] == 3, sz["critical"]
    tracez = json.load(urllib.request.urlopen(base + "/tracez"))
    traces = tracez["slowest"] + tracez["recent"]
    merged = next(
        t for t in traces if t["name"] == "leader.request"
    )["attrs"]["critical_path"]
    assert merged["critical_leg"] in ("helper", "local"), merged
    timeline = merged["timeline"]
    assert timeline and any(s["critical"] for s in timeline), merged
    for party in {s["party"] for s in timeline}:
        starts = [s["start_ms"] for s in timeline if s["party"] == party]
        assert starts == sorted(starts), (party, starts)
    assert all(s["start_ms"] >= 0.0 and s["duration_ms"] >= 0.0
               for s in timeline), timeline
print("critical-smoke: OK (/criticalz net+queue+compute == exchange "
      "rtt over real TCP, ~ raw rtt within overlap+uncertainty; "
      "/tracez merged timeline monotone per party)")
'

stage chaos-smoke env JAX_PLATFORMS=cpu python -c '
import os, tempfile, time, urllib.request
import numpy as np
from distributed_point_functions_tpu import heavy_hitters as hh
from distributed_point_functions_tpu.observability import AdminServer
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.robustness import failpoints
from distributed_point_functions_tpu.serving import (
    HelperSession, HelperUnavailable, InProcessTransport, LeaderSession,
    ServingConfig,
)
from distributed_point_functions_tpu.testing import encrypt_decrypt

reg = failpoints.default_failpoints()

# --- breaker-open: a dead helper leg must trip the breaker and then
# cost <1 ms per request (fast-fail), visible on /statusz. -------------
builder = DenseDpfPirDatabase.Builder()
rng = np.random.default_rng(0)
for _ in range(16):
    builder.insert(bytes(rng.integers(0, 256, 8, dtype=np.uint8)))
db = builder.build()
config = ServingConfig(
    max_batch_size=2, max_wait_ms=1.0, helper_retries=0,
    helper_backoff_ms=1.0, helper_backoff_max_ms=1.0,
    breaker_failure_threshold=2, breaker_reset_ms=60_000.0,
)
reg.arm("service.helper_leg", "error", times=None)
helper = HelperSession(db, encrypt_decrypt.decrypt, config)
leader = LeaderSession(db, InProcessTransport(helper.handle_wire), config)
client = DenseDpfPirClient.create(16, encrypt_decrypt.encrypt)
with helper, leader:
    for _ in range(2):
        request, _ = client.create_request([3])
        try:
            leader.handle_request(request)
            raise AssertionError("dead helper leg did not raise")
        except HelperUnavailable:
            pass
    assert leader.breaker.state == "open", leader.breaker_export()
    t0 = time.perf_counter()
    for _ in range(10):
        try:
            leader._send_to_helper(None, lambda: None)
            raise AssertionError("open breaker admitted a request")
        except HelperUnavailable:
            pass
    per_call = (time.perf_counter() - t0) / 10
    assert per_call < 1e-3, f"fast-fail cost {per_call * 1e3:.3f} ms"

    class Shim:
        export = staticmethod(leader.breaker_export)

    with AdminServer(registry=leader.metrics,
                     breakers={"leader.helper": Shim()}) as admin:
        statusz = urllib.request.urlopen(
            f"http://127.0.0.1:{admin.port}/statusz"
        ).read().decode()
        for needle in ("Circuit breakers", "leader.helper", "open"):
            assert needle in statusz, needle
reg.clear()

# --- sweep-resume: kill the sweep after round 0, resume a fresh Leader
# from the checkpoint, land on the plaintext answer. -------------------
values = [1, 1, 1, 9, 9, 14]
cfg = hh.HeavyHittersConfig(domain_bits=4, level_bits=2, threshold=2)
hh_client = hh.HeavyHittersClient(cfg)
pairs = [hh_client.generate_report(v) for v in values]
keys0, keys1 = [p[0] for p in pairs], [p[1] for p in pairs]
transport = InProcessTransport(
    hh.HeavyHittersHelper(
        hh.HeavyHittersServer(cfg, keys1, allow_resume=True)
    ).handle_wire
)
ckpt = os.path.join(tempfile.mkdtemp(), "sweep.json")
reg.arm("transport.inproc.roundtrip", "error", times=None, after=1)
try:
    hh.HeavyHittersLeader(
        hh.HeavyHittersServer(cfg, keys0), transport, checkpoint=ckpt
    ).run()
    raise AssertionError("injected fault did not kill the sweep")
except Exception as e:
    assert "injected fault" in str(e), e
reg.clear()
assert os.path.exists(ckpt), "no checkpoint persisted before the crash"
resumed = hh.HeavyHittersLeader(
    hh.HeavyHittersServer(cfg, keys0, allow_resume=True),
    transport, checkpoint=ckpt,
)
result = resumed.run()
counters = resumed.metrics.export()["counters"]
assert result.as_dict() == hh.plaintext_heavy_hitters(values, cfg), (
    result.as_dict()
)
assert counters["hh.sweep_resumes"] == 1, counters
assert counters["hh.rounds"] == 1, counters  # only the killed round re-ran
assert not os.path.exists(ckpt)  # deleted on completion
print("chaos-smoke: OK (breaker-open fast-fail <1 ms + /statusz row, "
      "sweep resumed from checkpoint and matched plaintext)")
'

stage overload-smoke env JAX_PLATFORMS=cpu python -c '
import urllib.request
import numpy as np
from distributed_point_functions_tpu.capacity import (
    BrownoutController, TenantPolicy,
)
from distributed_point_functions_tpu.observability import AdminServer
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.serving import (
    Overloaded, PlainSession, ServingConfig,
)

builder = DenseDpfPirDatabase.Builder()
rng = np.random.default_rng(1)
for _ in range(16):
    builder.insert(bytes(rng.integers(0, 256, 8, dtype=np.uint8)))
db = builder.build()
config = ServingConfig(
    max_batch_size=4, max_wait_ms=1.0, admission_enabled=True
)
client = DenseDpfPirClient.create(16, lambda pt, ci: pt)
request = client.create_plain_requests([3])[0]
with PlainSession(db, config) as session:
    want = session.handle_request(request).dpf_pir_response.masked_response

    # --- synthetic burst: a tiny tenant quota must shed at admission
    # with a typed RetryAfter hint, before any batching/evaluation. ----
    session.set_tenant("burst", TenantPolicy(rate_qps=1.0, burst=1.0))
    got = session.handle_request(request, tenant="burst")
    assert got.dpf_pir_response.masked_response == want
    hint = None
    sheds = 0
    for _ in range(5):
        try:
            session.handle_request(request, tenant="burst")
            raise AssertionError("burst past the quota was admitted")
        except Overloaded as e:
            sheds += 1
            hint = e
    assert hint.retry_after_s > 0 and hint.reason == "quota", vars(hint)
    counters = session.metrics.export()["counters"]
    assert counters["plain.admission.shed{reason=quota}"] == sheds, counters

    # --- brownout: a breaching signal walks the ladder to the top,
    # shows on /statusz, and fully auto-reverts once healthy. ----------
    breaching = {"v": True}
    brown = BrownoutController(
        signal=lambda: breaching["v"],
        engage_after_s=0.0, escalate_after_s=0.0, revert_after_s=0.0,
        metrics=session.metrics,
    )
    session.attach_brownout(brown, batch_cap=2, cheap_tier="streaming")
    for _ in range(4):
        brown.evaluate()
    assert brown.export()["level"] == 4, brown.export()
    with AdminServer(registry=session.metrics, brownout=brown,
                     admission=session.admission) as admin:
        statusz = urllib.request.urlopen(
            f"http://127.0.0.1:{admin.port}/statusz"
        ).read().decode()
        for needle in ("Brownout ladder", "critical_only",
                       "Admission", "burst"):
            assert needle in statusz, needle
    breaching["v"] = False
    for _ in range(4):
        brown.evaluate()
    assert brown.export()["level"] == 0, brown.export()
    # Knobs restored: the default tenant (shed at critical_only) serves
    # again, bit-identical.
    got = session.handle_request(request).dpf_pir_response.masked_response
    assert got == want
print("overload-smoke: OK (quota burst shed at admission with "
      f"RetryAfter={hint.retry_after_s:.2f}s, brownout ladder walked "
      "to critical_only on /statusz and fully reverted)")
'

stage prober-smoke env JAX_PLATFORMS=cpu \
    DPF_TPU_FAILPOINTS="transport.response=corrupt:times=none" \
    python -c '
import json, os, time, urllib.error, urllib.request
import numpy as np
from distributed_point_functions_tpu.observability import (
    AdminServer, BundleManager,
)
from distributed_point_functions_tpu.observability.events import (
    default_journal,
)
from distributed_point_functions_tpu.pir import DenseDpfPirDatabase
from distributed_point_functions_tpu.robustness import failpoints
from distributed_point_functions_tpu.serving import (
    HelperSession, InProcessTransport, LeaderSession, ServingConfig,
)
from distributed_point_functions_tpu.serving.prober import Prober
from distributed_point_functions_tpu.testing import encrypt_decrypt

# The env-armed corrupt failpoint must already be on the timeline
# (events.py emits retroactively for sites armed before import).
journal = default_journal()
armed = journal.tail(kind="failpoint.armed")
assert any(e["site"] == "transport.response" for e in armed), armed

rng = np.random.default_rng(7)
records = [bytes(rng.integers(0, 256, 8, dtype=np.uint8))
           for _ in range(16)]
builder = DenseDpfPirDatabase.Builder()
for r in records:
    builder.insert(r)
db = builder.build()
config = ServingConfig(
    max_batch_size=2, max_wait_ms=1.0, request_timeout_ms=None,
    helper_retries=0, helper_backoff_ms=1.0, breaker_reset_ms=50.0,
)
helper = HelperSession(db, encrypt_decrypt.decrypt, config)
leader = LeaderSession(db, InProcessTransport(helper.handle_wire), config)
bundles = BundleManager(cooldown_s=3600.0, max_bundles=4)
prober = Prober(
    leader, records, encrypter=encrypt_decrypt.encrypt,
    period_s=0.1, freshness_window_s=2.0,
)
prober.add_failure_listener(bundles.on_probe_failure)
# AdminServer registers the bundle sources (statusz/metrics/traces/
# events/probes), so it must exist before the first failing cycle.
with helper, leader, AdminServer(
    registry=leader.metrics, port=0, prober=prober, bundles=bundles
) as admin:
    base = f"http://127.0.0.1:{admin.port}"

    # 1. The prober must flag the corrupted helper leg within 3 cycles.
    flagged_cycle = None
    for cycle in range(3):
        results = prober.run_cycle()
        bad = [r for r in results
               if r["status"] in ("mismatch", "error")]
        if bad:
            flagged_cycle = cycle
            assert all(r["kind"] == "leader_e2e" for r in bad), bad
            break
    assert flagged_cycle is not None, "corruption not flagged in 3 cycles"
    # Plain-share probes bypass the transport: still bit-identical.
    by_kind = {r["kind"]: r["status"] for r in results}
    assert by_kind["pir_unbatched"] == "pass", by_kind

    # 2. Repeated failing cycles: exactly one bundle (cooldown).
    prober.run_cycle()
    prober.run_cycle()
    debugz = json.load(urllib.request.urlopen(base + "/debugz"))
    assert debugz["fired"] == 1, debugz
    assert len(debugz["bundles"]) == 1, debugz
    bundle = debugz["bundles"][0]
    assert bundle["reason"] == "probe_failure", bundle

    # 3. The bundle carries the correlated journal timeline.
    with open(os.path.join(bundle["path"], "events.json")) as f:
        kinds = {e["kind"] for e in json.load(f)["events"]}
    assert "failpoint.armed" in kinds, kinds
    assert kinds & {"prober.mismatch", "prober.error"}, kinds
    with open(os.path.join(bundle["path"], "probes.json")) as f:
        snap = json.load(f)
    assert snap["mismatches"] + snap["errors"] >= 1, snap
    # /eventz shows the same correlated timeline live.
    eventz = urllib.request.urlopen(base + "/eventz").read().decode()
    assert "failpoint.armed" in eventz, eventz
    assert "prober." in eventz, eventz

    # 4. The e2e probe never passed: once the freshness window elapses
    # /healthz must degrade to 503 (identity probes refresh in the
    # cycle, so only the e2e kind is stale).
    time.sleep(2.1)
    prober.run_cycle()
    try:
        urllib.request.urlopen(base + "/healthz")
        raise AssertionError("stale e2e probe did not degrade healthz")
    except urllib.error.HTTPError as e:
        assert e.code == 503, e.code
        detail = json.loads(e.read())
        assert "leader_e2e" in detail["stale_probes"], detail

    # 5. Clear the failpoint: full recovery. The breaker may still be
    # open for up to breaker_reset_ms after the last corrupted call,
    # so allow a few cycles for the half-open probe to close it.
    failpoints.default_failpoints().clear()
    deadline = time.time() + 30.0
    while True:
        results = prober.run_cycle()
        if all(r["status"] == "pass" for r in results):
            break
        assert time.time() < deadline, results
        time.sleep(0.1)
    probez = json.load(urllib.request.urlopen(base + "/probez"))
    statuses = {k: v["last_status"]
                for k, v in probez["freshness"].items()}
    assert set(statuses.values()) == {"pass"}, statuses
    health = json.load(urllib.request.urlopen(base + "/healthz"))
    assert health["status"] == "ok", health
    assert journal.tail(kind="prober.recovered"), "no recovery event"
    assert journal.tail(kind="failpoint.disarmed"), "no disarm event"
print("prober-smoke: OK (corruption flagged in cycle "
      f"{flagged_cycle}, one bundle with correlated timeline, "
      "healthz degraded on stale e2e probe and recovered after clear)")
'

stage capacity-accuracy-smoke env JAX_PLATFORMS=cpu \
    DPF_TPU_COSTMODEL_WINDOW=4 \
    DPF_TPU_COSTMODEL_DRIFT_WINDOWS=1 \
    DPF_TPU_COSTMODEL_MIN_SAMPLES=4 \
    DPF_TPU_COSTMODEL_MISPRICE=pir=3.0 \
    python -c '
import json, os, tempfile, threading, urllib.request
import numpy as np
from distributed_point_functions_tpu.capacity import (
    KILL_SWITCH_ENV, CapacityModel, ThroughputCalibration,
    set_default_capacity_model,
)
from distributed_point_functions_tpu.observability import (
    AdminServer, CostLedger, set_default_cost_ledger,
)
from distributed_point_functions_tpu.observability.costmodel import (
    DRIFT_GAUGE,
)
from distributed_point_functions_tpu.observability.events import (
    default_journal,
)
from distributed_point_functions_tpu.pir import DenseDpfPirDatabase
from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
from distributed_point_functions_tpu.pir.server import DenseDpfPirServer
from distributed_point_functions_tpu.serving import (
    PlainSession, ServingConfig,
)

records = [(b"cap-%02d:" % i).ljust(16, b".")[:16] for i in range(32)]
builder = DenseDpfPirDatabase.Builder()
for r in records:
    builder.insert(r)
database = builder.build()

# Pinned absurdly-fast calibration: every measured batch then looks
# enormously more expensive than priced on any host, so the mispriced
# workload drifts deterministically.
cal = tempfile.NamedTemporaryFile("w", suffix=".jsonl", delete=False)
cal.write(json.dumps({"metric": "serving_closed_loop_queries_per_sec",
                      "value": 1e9}) + "\n")
cal.write(json.dumps({"metric": "heavy_hitters_sweep_lanes_per_sec",
                      "value": 1e9}) + "\n")
cal.close()
model = CapacityModel(device_memory_bytes=16 << 30,
                      calibration=ThroughputCalibration(cal.name))
set_default_capacity_model(model)
set_default_cost_ledger(CostLedger())
raw_1key_ms = 3.0 * 1e3 / 1e9  # misprice only, no correction

client = DenseDpfPirClient.create(len(records), lambda pt, ci: pt)
reqs = [client.create_plain_requests([i])[0] for i in range(8)]
oracle_server = DenseDpfPirServer.create_plain(database)
oracle = [oracle_server.handle_plain_request(r)
          .dpf_pir_response.masked_response for r in reqs]

journal = default_journal()
config = ServingConfig(max_batch_size=1, max_wait_ms=1.0)
with PlainSession(database, config) as session:
    results = [None] * len(reqs)

    def worker(i):
        results[i] = session.handle_request(reqs[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for got, want in zip(results, oracle):
        assert got.dpf_pir_response.masked_response == want, \
            "responses changed under mispricing"
    with AdminServer(registry=session.metrics,
                     capacity=session.capacity_accuracy) as admin:
        base = "http://127.0.0.1:%d" % admin.port
        state = json.load(
            urllib.request.urlopen(base + "/capacityz?format=json"))
        pir_cells = {k: v for k, v in state["ledger"]["cells"].items()
                     if k.startswith("pir/")}
        assert pir_cells, state["ledger"]["cells"]
        for c in pir_cells.values():
            assert c["samples"] >= 1, c
            assert np.isfinite(c["residual_p50"]), c
    drifts = [e for e in journal.tail(n=64, kind="capacity.drift")
              if e.get("workload") == "pir"]
    assert drifts and drifts[0]["state"] == "drifting", drifts
    gauge = session.metrics.export()["gauges"][DRIFT_GAUGE]
    assert gauge >= 1.0, gauge
    rec = session.capacity_accuracy.recalibrator
    factor = rec.factor("pir")
    assert 1.0 < factor <= 2.0, factor
    priced = model.price_pir_keys(1).device_ms
    assert abs(priced - factor * raw_1key_ms) < 1e-12, (priced, factor)
    os.environ[KILL_SWITCH_ENV] = "0"
    try:
        reverted = model.price_pir_keys(1).device_ms
        assert abs(reverted - raw_1key_ms) < 1e-12, reverted
        assert journal.tail(kind="capacity.correction_reverted"), \
            "no revert event"
    finally:
        del os.environ[KILL_SWITCH_ENV]
    resumed = model.price_pir_keys(1).device_ms
    assert abs(resumed - factor * raw_1key_ms) < 1e-12, resumed
os.unlink(cal.name)
print("capacity-accuracy-smoke: OK (%d pir cells, drift journaled, "
      "gauge %.0f, correction clamped at %.2fx, kill switch "
      "reverted and resumed)" % (len(pir_cells), gauge, factor))
'

# --- rotation-smoke: rotate the database twice under live closed-loop
# traffic with a delay failpoint armed on snapshot.flip (stretching the
# Helper-first/Leader-last window), and prove the PR 12 contract: the
# prober stays bit-identical across both flips (goldens rotate with the
# generation), no response ever mixes generations, the q/s dip is
# bounded, and throughput recovers after the last flip.
stage rotation-smoke env JAX_PLATFORMS=cpu python -c '
import threading, time
import numpy as np
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase, messages,
)
from distributed_point_functions_tpu.pir.server import DenseDpfPirServer
from distributed_point_functions_tpu.robustness import failpoints
from distributed_point_functions_tpu.serving import (
    HelperSession, InProcessTransport, LeaderSession,
    RotationCoordinator, ServingConfig, SnapshotManager,
    SnapshotMismatch,
)
from distributed_point_functions_tpu.serving.prober import Prober
from distributed_point_functions_tpu.testing import encrypt_decrypt

NUM, NBYTES, FLIP_DELAY_MS = 32, 8, 25.0
rng = np.random.default_rng(12)
base = [bytes(rng.integers(0, 256, NBYTES, dtype=np.uint8))
        for _ in range(NUM)]
# Per-generation XOR masks differ pairwise, so records differ between
# any two generations at every byte: a torn cross-generation XOR can
# match no oracle.
recs = {g: [bytes(b ^ m for b in r) for r in base]
        for g, m in enumerate((0x00, 0xA5, 0x3C))}

def build(records):
    b = DenseDpfPirDatabase.Builder()
    for r in records:
        b.insert(r)
    return b.build()

def delta(prev, records):
    b = DenseDpfPirDatabase.Builder()
    for i, r in enumerate(records):
        b.update(i, r)
    return b.build_from(prev)

# Warm the jit buckets so a cold compile cannot masquerade as a dip.
warm = DenseDpfPirServer.create_plain(build(base))
keys = list(DenseDpfPirClient.create(NUM, lambda pt, ci: pt)
            .create_plain_requests([0])[0].plain_request.dpf_keys)
for b in (1, 2):
    warm.handle_plain_request(messages.PirRequest(
        plain_request=messages.PlainRequest(dpf_keys=keys * b)))

config = ServingConfig(max_batch_size=2, max_wait_ms=1.0)
helper = HelperSession(build(recs[0]), encrypt_decrypt.decrypt, config)
leader = LeaderSession(
    build(recs[0]), InProcessTransport(helper.handle_wire), config)
leader_mgr = SnapshotManager(leader)
helper_mgr = SnapshotManager(helper)
coordinator = RotationCoordinator(leader_mgr, helper_mgr)
prober = Prober(leader, recs[0], encrypter=encrypt_decrypt.encrypt,
                period_s=0.1, indices=[0, 7, 31])
prober.bind_snapshots(leader_mgr, records_provider=lambda g: recs[g])
prober.bind_snapshots(helper_mgr)

client = DenseDpfPirClient.create(NUM, encrypt_decrypt.encrypt)
lock = threading.Lock()
stats = {"completed": 0, "torn": 0, "refusals": 0}
times = []
stop = threading.Event()

def worker(tid):
    i = tid
    while not stop.is_set():
        idx = (7 * i) % NUM
        i += 2
        try:
            request, state = client.create_request([idx])
            got = client.handle_response(
                leader.handle_request(request), state)[0]
            now = time.monotonic()
            with lock:
                stats["completed"] += 1
                if not any(got == r[idx] for r in recs.values()):
                    stats["torn"] += 1
                times.append(now)
        except SnapshotMismatch:
            with lock:
                stats["refusals"] += 1

def qps(t0, t1):
    with lock:
        return sum(1 for t in times if t0 <= t < t1) / max(t1 - t0, 1e-9)

with helper, leader:
    assert all(r["status"] == "pass" for r in prober.run_cycle())
    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(2)]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    time.sleep(1.0)
    t1 = time.monotonic()
    failpoints.default_failpoints().arm(
        "snapshot.flip", "delay", times=4, delay_ms=FLIP_DELAY_MS)
    windows, staleness = [], []
    for gen in (1, 2):
        ldb = delta(leader.server.database, recs[gen])
        hdb = delta(helper.server.database, recs[gen])
        r0 = time.monotonic()
        report = coordinator.rotate(ldb, hdb)
        windows.append((r0, max(time.monotonic(), r0 + 0.25)))
        staleness.append(report["staleness_ms"])
        time.sleep(0.4)
    t2 = time.monotonic()
    time.sleep(1.0)
    t3 = time.monotonic()
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    # Goldens rotated with the flips: every probe passes on gen 2.
    results = prober.run_cycle()
    assert all(r["status"] == "pass" for r in results), results
    assert prober.export()["generation"] == 2, prober.export()

base_qps = qps(t0, t1)
rec_qps = qps(t2, t3)
worst = min(qps(w0, w1) for w0, w1 in windows)
dip_pct = max(0.0, (base_qps - worst) / base_qps * 100.0)
assert stats["torn"] == 0, stats
assert stats["completed"] > 0 and base_qps > 0, stats
# The armed delay stretched the window but it stayed bounded...
assert all(s >= FLIP_DELAY_MS * 0.8 for s in staleness), staleness
assert all(s < 5000.0 for s in staleness), staleness
# ...the dip is bounded (traffic never stopped) and recovers fully.
assert worst > 0, "throughput hit zero during rotation"
assert rec_qps >= 0.3 * base_qps, (rec_qps, base_qps)
snap = leader_mgr.export()
assert snap["serving_generation"] == 2 and snap["flips"] == 2, snap
assert snap["aborts"] == 0 and snap["retired_awaiting_drain"] == [], snap
completed = stats["completed"]
print("rotation-smoke: OK (2 rotations under load: staleness "
      f"{max(staleness):.1f} ms with {FLIP_DELAY_MS:.0f} ms flip delay "
      f"armed, dip {dip_pct:.0f}% of {base_qps:.0f} q/s baseline, "
      f"recovery {rec_qps:.0f} q/s, {completed} completed, 0 torn, "
      "prober bit-identical on generation 2)")
'

# --- shard-smoke: one logical Leader/Helper party served from a 2-D
# device mesh (4 database shards x 2 key lanes over 8 forced host
# devices), closed-loop traffic, one snapshot rotation at a batch
# boundary mid-traffic. Proves the PR 13 contract: every response is
# bit-identical to one generation's oracle (the 0xA5 mask makes a
# cross-generation mix match neither), the blackbox prober stays green
# through the flip with goldens rotating, and the flipped-to staging
# is fully sharded (all shards generation N+1, never a partial flip).
stage shard-smoke env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python -c '
import json, threading, time, urllib.request
import numpy as np
import jax
from distributed_point_functions_tpu.observability.admin import AdminServer
from distributed_point_functions_tpu.parallel.sharded import make_mesh2d
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.prng import xor_bytes
from distributed_point_functions_tpu.serving import (
    PlainSession, ServingConfig, SnapshotManager,
)
from distributed_point_functions_tpu.serving.prober import Prober

assert len(jax.devices()) == 8, jax.devices()
NUM, NBYTES = 512, 16
rng = np.random.default_rng(13)
base = [bytes(rng.integers(0, 256, NBYTES, dtype=np.uint8))
        for _ in range(NUM)]
recs = {0: base, 1: [bytes(b ^ 0xA5 for b in r) for r in base]}

def build(records):
    b = DenseDpfPirDatabase.Builder()
    for r in records:
        b.insert(r)
    return b.build()

def delta(prev, records):
    b = DenseDpfPirDatabase.Builder()
    for i, r in enumerate(records):
        b.update(i, r)
    return b.build_from(prev)

mesh = make_mesh2d(4, 2)
config = ServingConfig(max_batch_size=4, max_wait_ms=1.0)
client = DenseDpfPirClient(NUM, lambda pt, info: pt)
lock = threading.Lock()
stats = {"completed": 0, "torn": 0}
stop = threading.Event()

with PlainSession(build(recs[0]), config, mesh=mesh) as session:
    mgr = SnapshotManager(session)
    prober = Prober(session, recs[0], period_s=0.1, indices=[0, 7, 501])
    prober.bind_snapshots(mgr, records_provider=lambda g: recs[g])

    def query(indices):
        r0, r1 = client.create_plain_requests(indices)
        a = session.handle_request(r0).dpf_pir_response.masked_response
        b = session.handle_request(r1).dpf_pir_response.masked_response
        return [xor_bytes(x, y) for x, y in zip(a, b)]

    # Warm every mesh jit bucket traffic and probes can form, so the
    # flip below lands at a fast steady-state batch boundary instead of
    # queueing behind a cold multi-device compile.
    assert query([3])[0] == recs[0][3]
    query([3, 500])
    query([3, 500, 7, 101])
    assert session.server._mesh_plan is not None, \
        "mesh server fell back to single-device"
    assert all(r["status"] == "pass" for r in prober.run_cycle())

    def worker(tid):
        i = tid
        while not stop.is_set():
            idx = (7 * i) % NUM
            i += 2
            got = query([idx])[0]
            with lock:
                stats["completed"] += 1
                if not any(got == r[idx] for r in recs.values()):
                    stats["torn"] += 1
            stop.wait(0.01)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(2)]
    for t in threads:
        t.start()
    with prober:
        time.sleep(0.5)
        staged = mgr.stage(delta(session.server.database, recs[1]))
        assert staged > 0, "mesh staging transferred nothing"
        mgr.flip(timeout=60.0)
        time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    # Goldens rotated with the flip: the prober stays green on gen 1.
    results = prober.run_cycle()
    assert all(r["status"] == "pass" for r in results), results
    export = prober.export()
    assert export["mismatches"] == 0 and export["errors"] == 0, export
    snap = mgr.export()
    assert snap["serving_generation"] == 1 and snap["flips"] == 1, snap
    assert stats["torn"] == 0 and stats["completed"] > 0, stats
    assert query([3])[0] == recs[1][3]
    info = session.server.mesh_export()
    assert info["staging"]["generation"] == 1, info["staging"]
    # One row per device: 4 chunk shards x 2 key-axis replicas.
    per_dev = info["staging"]["shards"]
    assert len(per_dev) == 8, info["staging"]
    assert len({(s["chunk_start"], s["chunk_stop"]) for s in per_dev}) == 4
    assert session.server._mesh_plan is not None, "fell back post-flip"
    # The utilization ledger saw the mesh: every dispatch credits each
    # of the 4 chunk shards, so /utilz grows one busy row per shard.
    with AdminServer(registry=session.metrics) as admin:
        url = "http://127.0.0.1:%d/utilz?format=json" % admin.port
        util = json.load(urllib.request.urlopen(url))
        shard_rows = util["shards"]
        assert len(shard_rows) == 4, shard_rows
        assert all(row["busy_s"] > 0.0 for row in shard_rows.values()), \
            shard_rows
    completed = stats["completed"]
print("shard-smoke: OK (mesh 4x2 over 8 forced devices, 1 rotation "
      f"under load, {completed} completed, 0 torn, prober green on "
      "generation 1, staging sharded 4-ways, 4 shard rows on /utilz)")
'

# --- pipeline-smoke: the hot-path pipelining contract (ISSUE 14) end
# to end: closed-loop traffic through a depth-2 pipelined batcher with
# pipelined double-buffered staging on, one rotation fed by a
# `Builder.build_from` delta build (a handful of touched rows), and
# the observable signatures — zero prober failures through the flip,
# `/statusz` showing nonzero hidden (overlapped) transfer time with
# fewer db_staging syncs than copies, and the rotation prestage saving
# bytes over a full-image staging (`rotation_prestage_bytes_saved`).
stage pipeline-smoke env JAX_PLATFORMS=cpu \
    DPF_TPU_PIPELINED_STAGING=1 python -c '
import json, threading, time, urllib.request
import numpy as np
from distributed_point_functions_tpu.observability.admin import AdminServer
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.prng import xor_bytes
from distributed_point_functions_tpu.serving import (
    PlainSession, ServingConfig, SnapshotManager,
)
from distributed_point_functions_tpu.serving.prober import Prober

NUM, NBYTES, TOUCHED = 256, 16, 12
rng = np.random.default_rng(14)
base = [bytes(rng.integers(0, 256, NBYTES, dtype=np.uint8))
        for _ in range(NUM)]
# Generation 1 rewrites only TOUCHED rows — the delta prestage must
# ship just those (plus the index vector), not the full image. The
# updated rows differ from their gen-0 bytes everywhere (XOR 0x5A), so
# a torn read of an updated row matches neither oracle.
updated = sorted(rng.choice(NUM, size=TOUCHED, replace=False).tolist())
recs = {0: base, 1: list(base)}
for i in updated:
    recs[1][i] = bytes(b ^ 0x5A for b in base[i])

def build(records):
    b = DenseDpfPirDatabase.Builder()
    for r in records:
        b.insert(r)
    return b.build()

def delta(prev):
    b = DenseDpfPirDatabase.Builder()
    for i in updated:
        b.update(i, recs[1][i])
    return b.build_from(prev)

config = ServingConfig(max_batch_size=4, max_wait_ms=1.0,
                       pipeline_depth=2)
client = DenseDpfPirClient(NUM, lambda pt, info: pt)
lock = threading.Lock()
stats = {"completed": 0, "torn": 0}
stop = threading.Event()

with PlainSession(build(recs[0]), config) as session:
    mgr = SnapshotManager(session)
    prober = Prober(session, recs[0], period_s=0.1,
                    indices=[0, updated[0], NUM - 1])
    prober.bind_snapshots(mgr, records_provider=lambda g: recs[g])

    def query(indices):
        r0, r1 = client.create_plain_requests(indices)
        a = session.handle_request(r0).dpf_pir_response.masked_response
        b = session.handle_request(r1).dpf_pir_response.masked_response
        return [xor_bytes(x, y) for x, y in zip(a, b)]

    # Warm the jit buckets, then confirm the batcher really is
    # pipelined (depth-2 completion thread, not the serial fallback).
    assert query([3])[0] == recs[0][3]
    query([3, updated[0], 7, 101])
    gauges = session.metrics.export()["gauges"]
    assert gauges.get("plain.batcher.pipeline_depth") == 2.0, gauges
    assert all(r["status"] == "pass" for r in prober.run_cycle())

    def worker(tid):
        i = tid
        while not stop.is_set():
            idx = (7 * i) % NUM
            i += 2
            got = query([idx])[0]
            with lock:
                stats["completed"] += 1
                if not any(got == r[idx] for r in recs.values()):
                    stats["torn"] += 1
            stop.wait(0.01)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(2)]
    for t in threads:
        t.start()
    with prober:
        time.sleep(0.4)
        staged = mgr.stage(delta(session.server.database))
        assert staged > 0, "delta prestage transferred nothing"
        mgr.flip(timeout=60.0)
        time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    # Zero prober failures: green before, during (bound cycles), after.
    results = prober.run_cycle()
    assert all(r["status"] == "pass" for r in results), results
    export = prober.export()
    assert export["mismatches"] == 0 and export["errors"] == 0, export
    snap = mgr.export()
    assert snap["serving_generation"] == 1 and snap["flips"] == 1, snap
    assert stats["torn"] == 0 and stats["completed"] > 0, stats
    assert query([updated[0]])[0] == recs[1][updated[0]]
    # rotation_prestage_bytes_saved > 0: the delta rotation shipped a
    # fraction of the full image and SnapshotManager surfaced it.
    last_stage = snap["last_stage"]
    assert last_stage is not None, snap
    assert last_stage["mode"] == "delta", last_stage
    assert last_stage["bytes_saved"] > 0, last_stage
    assert last_stage["bytes_staged"] + last_stage["bytes_saved"] == \
        last_stage["bytes_full_image"], last_stage
    # /statusz shows the pipelined-staging signature: nonzero hidden
    # (overlapped) ms and strictly fewer db_staging syncs than copies.
    with AdminServer(registry=session.metrics, snapshots=mgr,
                     prober=prober) as admin:
        url = "http://127.0.0.1:%d/statusz" % admin.port
        state = json.load(urllib.request.urlopen(url + "?format=json"))
        transfers = state["device"]["transfers"]
        assert transfers["totals"]["overlapped_ms"] > 0.0, \
            transfers["totals"]
        db_phase = transfers["phases"]["db_staging"]
        assert db_phase["syncs"] < db_phase["h2d_copies"], db_phase
        html = urllib.request.urlopen(url).read().decode()
        assert "hidden behind host work" in html
    completed = stats["completed"]
    saved = last_stage["bytes_saved"]
    full_image = last_stage["bytes_full_image"]
    hidden_ms = transfers["totals"]["overlapped_ms"]
print("pipeline-smoke: OK (depth-2 batcher, 1 delta rotation under "
      f"load, {completed} completed, 0 torn, prober green on "
      f"generation 1, prestage saved {saved} of {full_image} bytes, "
      f"overlapped {hidden_ms:.1f} ms hidden)")
'

# --- util-smoke: the device-seconds ledger (ISSUE 15) end to end.
# Closed-loop traffic with think-time gaps through a real Leader /
# Helper pair must land a nonzero duty cycle on /utilz whose bubble
# breakdown is populated with typed causes summing to the measured
# idle; the flight-data sampler (driven on a synthetic 1 Hz clock so
# the stage is fast and deterministic) accrues >= 60 s of history; an
# injected 80 ms delay failpoint on the in-process helper leg spikes
# the helper-latency p99 past the anomaly watch band and must journal
# a util.anomaly; and a debug bundle captured after the stall must
# carry the full time-series history plus the anomaly in its journal
# tail.
stage util-smoke env JAX_PLATFORMS=cpu python -c '
import json, os, time, urllib.request
import numpy as np
from distributed_point_functions_tpu.observability import (
    events as events_mod,
)
from distributed_point_functions_tpu.observability.admin import AdminServer
from distributed_point_functions_tpu.observability.bundle import (
    BundleManager,
)
from distributed_point_functions_tpu.observability.timeseries import (
    MetricsSampler,
)
from distributed_point_functions_tpu.observability.utilization import (
    default_utilization_tracker,
)
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.robustness.failpoints import (
    default_failpoints,
)
from distributed_point_functions_tpu.serving import (
    HelperSession, LeaderSession, ServingConfig,
)
from distributed_point_functions_tpu.serving.transport import (
    InProcessTransport,
)
from distributed_point_functions_tpu.testing import encrypt_decrypt

NUM, NBYTES = 256, 16
rng = np.random.default_rng(15)
recs = [bytes(rng.integers(0, 256, NBYTES, dtype=np.uint8))
        for _ in range(NUM)]
builder = DenseDpfPirDatabase.Builder()
for r in recs:
    builder.insert(r)
db = builder.build()

config = ServingConfig(max_batch_size=4, max_wait_ms=1.0)
tracker = default_utilization_tracker()
with HelperSession(db, encrypt_decrypt.decrypt, config) as helper, \
        LeaderSession(db, InProcessTransport(helper.handle_wire),
                      config) as leader:
    client = DenseDpfPirClient.create(NUM, encrypt_decrypt.encrypt)

    def query(indices):
        request, state = client.create_request(indices)
        return client.handle_response(
            leader.handle_request(request), state
        )

    assert query([3]) == [recs[3]]
    # Closed-loop traffic with think-time gaps: the worker sees both
    # busy evals and typed idle bubbles (empty_queue / batch_wait).
    for i in range(24):
        idx = (7 * i) % NUM
        assert query([idx]) == [recs[idx]]
        time.sleep(0.005)

    # Flight-data sampler on a synthetic 1 Hz clock: fake timestamps
    # end at the real monotonic clock so ring-horizon checks against
    # the live clock keep every point. The helper_net phase reservoir
    # is the stall-sensitive series: unlike the end-to-end latency
    # histograms (whose p99 is pinned at the ~seconds first-compile
    # outlier), its p99 sits at ~1 ms until the failpoint fires.
    sampler = MetricsSampler(
        registry=leader.metrics, utilization=tracker, jitter_frac=0.0,
        include=("util.", "leader.", "phase_ms{phase=helper_net"),
    )
    base = time.monotonic() - 75.0
    for i in range(70):
        if i % 10 == 0:
            query([(3 * i) % NUM])
        sampler.sample_once(now=base + i)

    # Inject the stall: 80 ms on every in-process helper roundtrip,
    # >> the ~1 ms baseline, so helper-latency p99 blows through the
    # 3x trailing-mean anomaly band on the next sample.
    fps = default_failpoints()
    fps.arm("transport.inproc.roundtrip", action="delay",
            delay_ms=80.0, times=3)
    try:
        for i in range(3):
            query([i])
    finally:
        fps.disarm("transport.inproc.roundtrip")
    sampler.sample_once(now=base + 71.0)

    anoms = events_mod.default_journal().tail(50, kind="util.anomaly")
    assert anoms, "injected stall journaled no util.anomaly"
    assert any(
        "helper_net" in e.get("series", "")
        and e.get("direction") == "spike"
        for e in anoms
    ), anoms

    bundles = BundleManager(cooldown_s=0.0, max_bundles=2)
    with AdminServer(registry=leader.metrics, utilization=tracker,
                     timeseries=sampler, bundles=bundles) as admin:
        url = "http://127.0.0.1:%d" % admin.port
        snap = json.load(
            urllib.request.urlopen(url + "/utilz?format=json")
        )
        totals = snap["totals"]
        duty = totals["duty_cycle_pct"]
        assert duty is not None and duty > 0.0, totals
        causes = totals["idle_s"]
        assert causes, "bubble breakdown empty"
        assert set(causes) & {"empty_queue", "batch_wait",
                              "admission_shed"}, causes
        # Attribution is complete: typed causes sum to measured idle
        # (each cause rounds independently, hence the tolerance).
        assert abs(sum(causes.values()) - totals["idle_total_s"]) \
            < 1e-3, totals
        ts = json.load(
            urllib.request.urlopen(url + "/timeseriesz?format=json")
        )
        assert ts["store"]["series_count"] > 0, ts

        entry = bundles.trigger(
            "injected_stall",
            {"site": "transport.inproc.roundtrip"},
        )
        assert entry is not None, "bundle capture suppressed"
        assert entry["sources"].get("timeseries") == "ok", entry
        with open(os.path.join(entry["path"], "timeseries.json")) as f:
            hist = json.load(f)
        spans = [
            pts[-1][0] - pts[0][0]
            for tiers in hist["store"]["series"].values()
            if len(pts := tiers.get("1s", [])) >= 2
        ]
        assert spans and max(spans) >= 60.0, \
            "bundle carries < 60 s of history"
        with open(os.path.join(entry["path"], "events.json")) as f:
            journal_tail = json.load(f)
        assert any(
            e.get("kind") == "util.anomaly"
            for e in journal_tail["events"]
        ), "anomaly missing from bundle journal tail"
        history_s = max(spans)
print(f"util-smoke: OK (duty cycle {duty:.1f}%, "
      f"{len(causes)} bubble causes summing to idle, util.anomaly "
      f"journaled after 80 ms injected stall, bundle carries "
      f"{history_s:.0f} s of flight data)")
'

stage sparse-smoke env JAX_PLATFORMS=cpu python -c '
import threading, time
from distributed_point_functions_tpu.pir.cuckoo_database import (
    CuckooHashedDpfPirDatabase,
)
from distributed_point_functions_tpu.pir.sparse_client import KeyNotFound
from distributed_point_functions_tpu.pir.sparse_server import (
    CuckooHashingSparseDpfPirServer,
)
from distributed_point_functions_tpu.serving import (
    ServingConfig, SnapshotManager, SparsePlainSession,
    make_sparse_client, sparse_lookup_plain,
)
from distributed_point_functions_tpu.serving.prober import Prober

NUM = 48
# Fixed-width keys and values: a delta rotation preserves the packed
# row width of each dense store, so upserts must stay in-width.
records = {b"key_%02d" % i: b"val_%02d" % i for i in range(NUM)}
params = CuckooHashingSparseDpfPirServer.generate_params(
    NUM, seed=b"0123456789abcdef"
)
builder = CuckooHashedDpfPirDatabase.Builder().set_params(params)
for kv in records.items():
    builder.insert(kv)
db = builder.build()

session = SparsePlainSession(
    params, db, ServingConfig(max_batch_size=8, max_wait_ms=1.0)
)
client = make_sparse_client(session)
manager = SnapshotManager(session)
new_records = dict(records)
new_records[b"key_02"] = b"VAL_02"
new_records[b"new_01"] = b"val_99"
prober = Prober(session, sparse_records=records, period_s=0.1)
prober.bind_snapshots(manager, lambda gen: new_records)

# Warm: makes the gen-0 stagings resident (prereq for a delta
# prestage) and pays the jit compile outside the loaded window.
warm = sparse_lookup_plain(session, client, [b"key_05", b"absent"])
assert warm[0] == b"val_05" and isinstance(warm[1], KeyNotFound), warm

stop = threading.Event()
failures, served = [], [0]

def traffic():
    while not stop.is_set():
        # Two-share lookups pin the manager so the armed flip cannot
        # land between the shares (cross-generation XOR is garbage).
        with manager.pin():
            out = sparse_lookup_plain(
                session, client, [b"key_05", b"absent"]
            )
        if out[0] != b"val_05" or not isinstance(out[1], KeyNotFound):
            failures.append(out)
            return
        served[0] += 2
        time.sleep(0.02)

threads = [threading.Thread(target=traffic) for _ in range(2)]
for t in threads:
    t.start()
try:
    assert all(
        r["status"] == "pass" for r in prober.run_cycle()
    ), prober.export()
    delta = CuckooHashedDpfPirDatabase.Builder()
    delta.insert((b"key_02", b"VAL_02"))
    delta.insert((b"new_01", b"val_99"))
    db1 = delta.build_from(db)
    staged = manager.stage(db1)
    assert staged > 0
    stats = db1.last_prestage_stats
    assert stats is not None and stats["mode"] == "delta", stats
    assert stats["bytes_saved"] > 0, stats
    assert (
        stats["bytes_staged"] + stats["bytes_saved"]
        == stats["bytes_full_image"]
    ), stats
    manager.flip(timeout=120.0)
    assert all(
        r["status"] == "pass" for r in prober.run_cycle()
    ), prober.export()
finally:
    stop.set()
    for t in threads:
        t.join(timeout=60)
assert not failures, failures[:1]
assert manager.serving_generation() == 1

out = sparse_lookup_plain(
    session, client, [b"key_02", b"new_01", b"key_07", b"absent"]
)
assert out[0] == b"VAL_02" and out[1] == b"val_99", out
assert out[2] == b"val_07", out
assert isinstance(out[3], KeyNotFound) and not out[3], out

export = prober.export()
assert export["mismatches"] == 0 and export["errors"] == 0, export
assert export["generation"] == 1, export
kinds = set(export["freshness"])
assert kinds == {"sparse_kv", "sparse_absent"}, kinds
assert all(v["identity"] for v in export["freshness"].values())
snap = manager.export()
assert snap["serving_generation"] == 1 and snap["flips"] == 1, snap
print(
    "sparse-smoke: OK (%d lookups under load, delta rotation saved "
    "%d of %d bytes, %d probes all green, absent key stayed typed "
    "not-found)" % (
        served[0], stats["bytes_saved"], stats["bytes_full_image"],
        export["probes"],
    )
)
'

# --- fleet-smoke: ISSUE 18 end to end. A 3-replica in-process fleet
# serves closed-loop tenants through the price-aware front door while
# one quorum rotation runs with a replica killed mid-stage (failpoint
# on its per-replica chaos site). Asserts: zero wrong bits ever served
# (every reconstruction matches the oracle of SOME single generation),
# quorum held (2/3) so the fleet committed, the laggard was shed,
# converged party by party, and readmitted, and /fleetz reflects the
# final state — 3 serving replicas all at the new generation.
stage fleet-smoke env JAX_PLATFORMS=cpu python -c '
import contextlib, json, threading, time, urllib.request
import numpy as np
from distributed_point_functions_tpu.fleet import (
    FleetRotationCoordinator, FleetRouter, Replica, ReplicaSet,
)
from distributed_point_functions_tpu.observability import AdminServer
from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
from distributed_point_functions_tpu.pir.database import (
    DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.prng import xor_bytes
from distributed_point_functions_tpu.robustness import failpoints
from distributed_point_functions_tpu.serving import (
    PlainSession, ServingConfig, SnapshotManager,
)
from distributed_point_functions_tpu.serving.batcher import Overloaded

NUM, NB = 64, 16
rng = np.random.default_rng(77)
R0 = [bytes(rng.integers(0, 256, NB, dtype=np.uint8)) for _ in range(NUM)]
R1 = [bytes(b ^ 0xA5 for b in r) for r in R0]  # differs at every byte

def full(records):
    b = DenseDpfPirDatabase.Builder()
    for r in records:
        b.insert(r)
    return b.build()

def delta(prev, records):
    b = DenseDpfPirDatabase.Builder()
    for i, r in enumerate(records):
        b.update(i, r)
    return b.build_from(prev)

cfg = ServingConfig(max_batch_size=8, max_wait_ms=2.0)
rs = ReplicaSet()
reps = []
for i in range(3):
    s = PlainSession(full(R0), cfg)
    reps.append(
        rs.add(Replica("r%d" % i, s, leader_snapshots=SnapshotManager(s)))
    )
router = FleetRouter(rs)
client = DenseDpfPirClient(NUM, lambda pt, info: pt)
w0, w1 = client.create_plain_requests([0])
for r in reps:  # warm the jit bucket on every replica
    r.leader.handle_request(w0)
    r.leader.handle_request(w1)

oracles = [R0, R1]
stats = {"done": 0, "wrong": 0, "sheds": 0}
lock = threading.Lock()
stop = threading.Event()

def worker(tid):
    tenant = "t%d" % tid
    i = tid
    while not stop.is_set():
        idx = (i * 7) % NUM
        i += 1
        try:
            rep = router.pick(tenant)
            q0, q1 = client.create_plain_requests([idx])
            # Pin the replica so both halves of the golden pair answer
            # from ONE generation (cross-generation XOR is garbage).
            with contextlib.ExitStack() as st:
                for m in rep.managers():
                    st.enter_context(m.pin())
                a = rep.leader.handle_request(q0)
                b = rep.leader.handle_request(q1)
            got = xor_bytes(
                a.dpf_pir_response.masked_response[0],
                b.dpf_pir_response.masked_response[0],
            )
            with lock:
                stats["done"] += 1
                if not any(got == recs[idx] for recs in oracles):
                    stats["wrong"] += 1
        except Overloaded:
            with lock:
                stats["sheds"] += 1
            time.sleep(0.002)
        time.sleep(0.001)  # unpinned window: never starve the flip

threads = [
    threading.Thread(target=worker, args=(t,)) for t in range(3)
]
for t in threads:
    t.start()
time.sleep(0.3)

# One quorum rotation with r1 killed mid-stage: quorum 2/3 holds, the
# laggard is shed, converged, and readmitted while traffic flows.
failpoints.default_failpoints().arm("fleet.stage.r1", "error", times=1)
coord = FleetRotationCoordinator(rs)
report = coord.rotate(
    lambda rep: (delta(rep.leader.server.database, R1), None)
)
assert report["to_generation"] == 1, report
assert sorted(report["acked"]) == ["r0", "r2"], report
assert report["laggards"] == {"r1": "recovered"}, report
time.sleep(0.3)
stop.set()
for t in threads:
    t.join(timeout=10)
failpoints.default_failpoints().clear()

assert stats["done"] > 0 and stats["wrong"] == 0, stats
export = rs.export()
assert export["sheds"] == 1 and export["readmissions"] == 1, export
assert all(r.serving_generation() == 1 for r in reps)
with AdminServer(fleet=rs) as admin:
    url = "http://127.0.0.1:%d/fleetz" % admin.port
    state = json.loads(urllib.request.urlopen(url, timeout=10).read())
assert state["counts"] == {
    "serving": 3, "staging": 0, "draining": 0, "dead": 0
}, state["counts"]
assert all(
    row["serving_generation"] == 1
    for row in state["replicas"].values()
), state["replicas"]
for r in reps:
    r.leader.close()
print(
    "fleet-smoke: OK (%d lookups across 3 replicas, 0 wrong bits, "
    "quorum rotation -> generation 1 with r1 killed mid-stage: "
    "laggard shed + readmitted, /fleetz all serving)" % stats["done"]
)
'

# --- fleet-obs-smoke: ISSUE 19 end to end. The same 3-replica fleet
# under closed-loop traffic, now with the fleet telemetry plane
# attached: per-replica scopes feeding one aggregator, a quorum
# rotation with a DELAY failpoint on r1's stage site, then a forced
# divergence (r2 staged different records at the same generation).
# Asserts: /fleet-statusz?format=json carries per-replica rows AND the
# merged view; the merged /fleet-timelinez tells the rotation story
# causally (every replica's snapshot.flip before the fleet.rotation
# commit event, each attributed to its replica); and the divergence
# produces EXACTLY ONE fleet-wide debug bundle holding all three
# replicas' sections plus the merged timeline.
stage fleet-obs-smoke env JAX_PLATFORMS=cpu python -c '
import contextlib, json, tempfile, threading, time, urllib.request
import numpy as np
from distributed_point_functions_tpu.fleet import (
    FleetRotationCoordinator, FleetRouter, FleetTelemetry, Replica,
    ReplicaSet,
)
from distributed_point_functions_tpu.observability import AdminServer
from distributed_point_functions_tpu.observability.bundle import (
    BundleManager,
)
from distributed_point_functions_tpu.observability.events import (
    EventJournal,
)
from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
from distributed_point_functions_tpu.pir.database import (
    DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.prng import xor_bytes
from distributed_point_functions_tpu.robustness import failpoints
from distributed_point_functions_tpu.serving import (
    PlainSession, ServingConfig, SnapshotManager,
)
from distributed_point_functions_tpu.serving.batcher import Overloaded
from distributed_point_functions_tpu.serving.metrics import MetricsRegistry
from distributed_point_functions_tpu.serving.prober import CrossReplicaProbe

NUM, NB = 64, 16
rng = np.random.default_rng(19)
R0 = [bytes(rng.integers(0, 256, NB, dtype=np.uint8)) for _ in range(NUM)]
R1 = [bytes(b ^ 0xA5 for b in r) for r in R0]
R1_BAD = [bytes(b ^ 0x3C for b in r) for r in R0]  # r2s forced skew

def full(records):
    b = DenseDpfPirDatabase.Builder()
    for r in records:
        b.insert(r)
    return b.build()

def delta(prev, records):
    b = DenseDpfPirDatabase.Builder()
    for i, r in enumerate(records):
        b.update(i, r)
    return b.build_from(prev)

cfg = ServingConfig(max_batch_size=8, max_wait_ms=2.0)
journal = EventJournal(capacity=256)
rs = ReplicaSet(journal=journal)
reps = []
for i in range(3):
    s = PlainSession(full(R0), cfg)
    reps.append(
        rs.add(Replica("r%d" % i, s, leader_snapshots=SnapshotManager(s)))
    )
fleet_registry = MetricsRegistry()
router = FleetRouter(rs, journal=journal, metrics=fleet_registry)
probe = CrossReplicaProbe(
    rs.healthy, R0,
    records_provider=lambda gen: {0: R0, 1: R1}.get(gen),
    journal=journal,
)
telemetry = FleetTelemetry(
    rs, router=router, probe=probe, journal=journal,
    registry=fleet_registry,
)
for r in reps:
    telemetry.scope(r)
bundle_dir = tempfile.mkdtemp(prefix="fleet-obs-smoke-")
bundles = BundleManager(
    directory=bundle_dir, cooldown_s=60.0, journal=journal,
)
telemetry.wire_bundles(bundles)

client = DenseDpfPirClient(NUM, lambda pt, info: pt)
w0, w1 = client.create_plain_requests([0])
for r in reps:  # warm the jit bucket on every replica
    r.leader.handle_request(w0)
    r.leader.handle_request(w1)
assert probe.run_cycle()["status"] == "pass"

stats = {"done": 0, "wrong": 0, "sheds": 0}
lock = threading.Lock()
stop = threading.Event()

def worker(tid):
    tenant = "t%d" % tid
    i = tid
    while not stop.is_set():
        idx = (i * 7) % NUM
        i += 1
        try:
            rep = router.pick(tenant)
            q0, q1 = client.create_plain_requests([idx])
            with contextlib.ExitStack() as st:
                for m in rep.managers():
                    st.enter_context(m.pin())
                a = rep.leader.handle_request(q0)
                b = rep.leader.handle_request(q1)
            got = xor_bytes(
                a.dpf_pir_response.masked_response[0],
                b.dpf_pir_response.masked_response[0],
            )
            with lock:
                stats["done"] += 1
                if not any(got == recs[idx] for recs in (R0, R1)):
                    stats["wrong"] += 1
        except Overloaded:
            with lock:
                stats["sheds"] += 1
            time.sleep(0.002)
        time.sleep(0.001)

threads = [
    threading.Thread(target=worker, args=(t,), daemon=True)
    for t in range(3)
]
for t in threads:
    t.start()
telemetry.sample()
time.sleep(0.3)

# One quorum rotation with a DELAY failpoint on r1s stage site: a
# latency spike is not a fault, so the fleet commits. Under closed-
# loop traffic a replica may still miss the drain window and lag --
# acceptable only if the coordinator converged and readmitted it
# before returning. The telemetry resample hooked to the coordinator
# refreshes staleness right at the commit.
failpoints.default_failpoints().arm(
    "fleet.stage.r1", "delay", delay_ms=100, times=1
)
coord = FleetRotationCoordinator(rs, journal=journal)
coord.set_telemetry(telemetry)
report = coord.rotate(
    lambda rep: (delta(rep.leader.server.database, R1), None)
)
assert report["to_generation"] == 1, report
assert set(report["laggards"].values()) <= {"recovered"}, report
time.sleep(0.3)
telemetry.sample()
stop.set()
for t in threads:
    t.join(timeout=10)
failpoints.default_failpoints().clear()
assert stats["done"] > 0 and stats["wrong"] == 0, stats
assert probe.run_cycle()["status"] == "pass"

with AdminServer(fleet=rs, fleet_telemetry=telemetry) as admin:
    base = "http://127.0.0.1:%d" % admin.port
    state = json.loads(urllib.request.urlopen(
        base + "/fleet-statusz?format=json", timeout=10).read())
    # Per-replica rows AND the merged view, in one document.
    assert sorted(state["replicas"]) == ["r0", "r1", "r2"], sorted(
        state["replicas"])
    per_replica_counts = {}
    for rid, scrape in state["replicas"].items():
        assert scrape["state"] == "serving", (rid, scrape["state"])
        hist = scrape["metrics"]["histograms"]["plain.request_ms"]
        per_replica_counts[rid] = hist["count"]
        assert hist["count"] > 0, (rid, hist)
    merged_hist = state["merged"]["histograms"]["plain.request_ms"]
    assert merged_hist["count"] == sum(per_replica_counts.values())
    assert merged_hist["replicas"] == ["r0", "r1", "r2"]
    assert state["verdict"]["status"] == "ok", state["verdict"]
    slo_states = {
        o["name"]: o["state"] for o in state["slo"]["objectives"]
    }
    assert slo_states["fleet_routable_floor"] == "ok", slo_states

    # The merged timeline tells the rotation story causally: every
    # replica flipped (each snapshot.flip attributed to its replica)
    # BEFORE the fleet.rotation commit event.
    timeline = json.loads(urllib.request.urlopen(
        base + "/fleet-timelinez?format=json&n=256", timeout=10).read())
    events = timeline["events"]
    flips = {
        e["replica"]: i for i, e in enumerate(events)
        if e["kind"] == "snapshot.flip"
    }
    commits = [
        i for i, e in enumerate(events) if e["kind"] == "fleet.rotation"
    ]
    assert sorted(flips) == ["r0", "r1", "r2"], flips
    assert len(commits) == 1, commits
    assert all(i < commits[0] for i in flips.values()), (flips, commits)
    text = urllib.request.urlopen(
        base + "/fleet-timelinez?n=64", timeout=10).read().decode()
    assert "fleet.rotation" in text and "r1" in text

# Forced divergence: r2 stages DIFFERENT records and flips to the same
# generation number the quorum is about to reach -- two replicas now
# answer generation 2 with different bytes. The probe must catch it
# and the plane must capture EXACTLY ONE fleet-wide bundle.
coord.rotate(
    lambda rep: (
        delta(
            rep.leader.server.database,
            R1_BAD if rep.replica_id == "r2" else R0,
        ),
        None,
    )
)
result = probe.run_cycle()
assert result["status"] == "mismatch", result
probe.run_cycle()  # a second divergent cycle lands in the cooldown
export = bundles.export()
assert export["fired"] == 1, export
assert export["suppressed_cooldown"] >= 1, export
entry = export["bundles"][0]
assert entry["reason"] == "probe_failure", entry
for source in (
    "replica_r0", "replica_r1", "replica_r2",
    "fleet_timeline", "fleet_status",
):
    assert entry["sources"][source] == "ok", (source, entry["sources"])
with open(entry["path"] + "/fleet_timeline.json") as f:
    bundled = json.load(f)
assert any(
    e["kind"] == "fleet.divergence" for e in bundled["events"]
), [e["kind"] for e in bundled["events"]][-8:]
for r in reps:
    r.leader.close()
print(
    "fleet-obs-smoke: OK (%d lookups, /fleet-statusz per-replica+merged"
    ", causal rotation timeline, forced divergence -> 1 fleet bundle "
    "with all 3 replica sections)" % stats["done"]
)
'

# --- forecast-smoke: the act-before-burn loop end to end. Synthetic
# load ramps toward a deliberately lowered calibrated capacity on an
# injected clock (deterministic Holt fit); the page must land in the
# journal while the hard SLO has never burned, the governor must
# tighten visibly and revert exactly, and every response served during
# the drill must stay bit-identical to the oracle.
stage forecast-smoke env JAX_PLATFORMS=cpu python -c '
import json
import urllib.request
import numpy as np
from distributed_point_functions_tpu.capacity import TenantPolicy
from distributed_point_functions_tpu.capacity.admission import (
    PredictiveGovernor,
)
from distributed_point_functions_tpu.observability import (
    AdminServer, EventJournal, Forecaster, SloObjective, SloTracker,
    TimeSeriesStore, WorkloadObservatory,
)
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient, DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.serving import (
    PlainSession, ServingConfig,
)

t = [0.0]
clock = lambda: t[0]

builder = DenseDpfPirDatabase.Builder()
rng = np.random.default_rng(2)
for _ in range(16):
    builder.insert(bytes(rng.integers(0, 256, 8, dtype=np.uint8)))
db = builder.build()
client = DenseDpfPirClient.create(16, lambda pt, ci: pt)
request = client.create_plain_requests([5])[0]

config = ServingConfig(
    max_batch_size=4, max_wait_ms=1.0, admission_enabled=True
)
journal = EventJournal(capacity=128, clock=clock)
store = TimeSeriesStore(tiers=((1.0, 240),), max_series=8, clock=clock)

with PlainSession(db, config) as session:
    want = session.handle_request(request).dpf_pir_response.masked_response
    observatory = session.attach_workload(WorkloadObservatory())
    session.set_tenant("ramp", TenantPolicy(rate_qps=500.0))

    # The deliberately lowered calibrated capacity: 1% of the model.
    calibrated = session.admission.model.serving_queries_per_sec()
    lowered = max(1.0, 0.01 * calibrated)

    forecaster = Forecaster(
        store, window_s=60.0, horizon_s=120.0, page_horizon_s=120.0,
        min_points=8, registry=session.metrics, journal=journal,
        clock=clock,
    )
    forecaster.watch(
        "load.rate_qps", ceiling_source=lambda: lowered,
        label="offered load vs lowered calibrated capacity",
    )
    governor = PredictiveGovernor(
        session.admission, forecaster.min_time_to_breach_s,
        horizon_s=120.0, floor=0.25, metrics=session.metrics,
        clock=clock,
    )
    # The hard SLO the prediction must beat: offered load at or above
    # the lowered capacity.
    hard = SloTracker(
        [SloObjective(
            name="load_ceiling", kind="gauge_max",
            metric="load.rate_qps", threshold=lowered, severity="hard",
        )],
        session.metrics, clock=clock,
    )

    served = [0]
    def tick(rate):
        t[0] += 1.0
        store.record("load.rate_qps", rate)
        session.metrics.gauge("load.rate_qps").set(rate)
        got = session.handle_request(request, tenant="ramp")
        assert got.dpf_pir_response.masked_response == want
        served[0] += 1

    assert governor.update() == 1.0  # calm: policy as declared

    # --- the ramp: 60 synthetic seconds climbing from 20% to 70% of
    # the lowered capacity — never touching it. -----------------------
    for i in range(60):
        tick(lowered * (0.2 + 0.5 * i / 59.0))
    state = forecaster.run()
    ttb = state["min_time_to_breach_s"]
    assert ttb is not None and 0.0 < ttb < forecaster.horizon_s, state
    predicted = journal.tail(10, kind="forecast.breach_predicted")
    assert predicted, "breach_predicted missing from the journal"
    (burn,) = hard.evaluate()
    assert burn["state"] == "ok" and burn["burn_s"] == 0.0, burn

    # --- the governor tightens, visibly. -----------------------------
    scale = governor.update()
    assert scale < 1.0, scale
    adm = session.admission.export()
    assert adm["rate_scale"] == scale, adm
    assert adm["tenants"]["ramp"]["effective_rate_qps"] < 500.0, adm
    with AdminServer(registry=session.metrics, forecast=forecaster,
                     governor=governor) as admin:
        base = "http://127.0.0.1:%d" % admin.port
        fz = json.load(
            urllib.request.urlopen(base + "/forecastz?format=json")
        )
        assert fz["min_time_to_breach_s"] is not None, fz
        assert fz["min_time_to_breach_s"] < 120.0, fz
        assert fz["governor"]["scale"] < 1.0, fz
        cz = urllib.request.urlopen(base + "/capacityz").read().decode()
        assert "predictive governor: scale" in cz, cz
        assert "ramp: rate 500.0 ->" in cz, cz

    # --- the ramp recedes: forecast clears, exact revert. ------------
    for _ in range(70):
        tick(lowered * 0.2)
    assert forecaster.min_time_to_breach_s() is None
    assert governor.update() == 1.0
    adm = session.admission.export()
    assert adm["rate_scale"] == 1.0, adm
    assert adm["tenants"]["ramp"]["effective_rate_qps"] == 500.0, adm
    got = session.handle_request(request, tenant="ramp")
    assert got.dpf_pir_response.masked_response == want
    assert observatory.export()["observations"] >= served[0]
print(
    "forecast-smoke: OK (breach predicted %.0fs out with 0s of hard "
    "burn, governor tightened to x%.2f on /capacityz and reverted, "
    "%d bit-identical responses)" % (ttb, scale, served[0] + 2)
)
'

stage perf-gate python -m benchmarks.regression_gate --check-only \
    --history benchmarks/fixtures/history_fixture.jsonl

stage dryrun make -s dryrun

if [ "${FULL:-0}" = "1" ]; then
    stage test-full python -m pytest tests/ -q
fi

echo "presubmit: $([ $fail -eq 0 ] && echo PASS || echo FAIL)"
exit $fail
