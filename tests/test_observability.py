"""Observability tests: tracing, flight recorder, Prometheus exposition,
the admin endpoint, and cross-party trace propagation.

The end-to-end sections reuse the small serving fixture from
`test_serving_service` (128 x 16B records, real crypto) and the tiny
heavy-hitters domain from the demo smoke (8 bits, 2 levels), so the
traces asserted on here come out of the real Leader/Helper wire paths —
including the old-peer downgrade legs of both wire formats.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_point_functions_tpu import heavy_hitters as hh
from distributed_point_functions_tpu import serialization
from distributed_point_functions_tpu.observability import (
    AdminServer,
    exposition,
    propagation,
    tracing,
)
from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient,
    DenseDpfPirDatabase,
)
from distributed_point_functions_tpu.protos import (
    private_information_retrieval_pb2 as pir_pb2,
)
from distributed_point_functions_tpu.serving import (
    FramedTcpServer,
    HelperSession,
    InProcessTransport,
    LeaderSession,
    ServingConfig,
    TcpTransport,
)
from distributed_point_functions_tpu.serving.metrics import (
    MetricsRegistry,
    labeled_name,
)
from distributed_point_functions_tpu.testing import encrypt_decrypt

NUM_RECORDS = 128
RECORD_BYTES = 16
RNG = np.random.default_rng(4321)


def build_database():
    records = [
        bytes(RNG.integers(0, 256, RECORD_BYTES, dtype=np.uint8))
        for _ in range(NUM_RECORDS)
    ]
    builder = DenseDpfPirDatabase.Builder()
    for r in records:
        builder.insert(r)
    return builder.build(), records


DATABASE, RECORDS = build_database()


def make_config(**overrides):
    base = dict(
        max_batch_size=4,
        max_wait_ms=5.0,
        helper_timeout_ms=None,
        helper_retries=2,
        helper_backoff_ms=1.0,
        helper_backoff_max_ms=2.0,
    )
    base.update(overrides)
    return ServingConfig(**base)


def leader_helper_pair(transport_factory):
    helper = HelperSession(DATABASE, encrypt_decrypt.decrypt, make_config())
    leader = LeaderSession(
        DATABASE, transport_factory(helper.handle_wire), make_config()
    )
    return leader, helper


def run_query(leader, indices):
    client = DenseDpfPirClient.create(NUM_RECORDS, encrypt_decrypt.encrypt)
    request, state = client.create_request(indices)
    response = leader.handle_request(request)
    return client.handle_response(response, state)


@pytest.fixture
def recorder():
    """Swap in a fresh default flight recorder for one test."""
    prev = tracing.default_recorder()
    rec = tracing.set_default_recorder(tracing.FlightRecorder())
    yield rec
    tracing.set_default_recorder(prev)


# ---------------------------------------------------------------------------
# Tracing core: trace_request / span / flight recorder
# ---------------------------------------------------------------------------


def test_trace_request_roots_records_and_spans(recorder):
    with tracing.trace_request("t.request", role="test") as trace:
        assert tracing.current_trace() is trace
        with tracing.span("stage_a", detail=7):
            pass
    assert tracing.current_trace() is None
    dump = recorder.dump()
    assert dump["recorded"] == 1
    (slow,) = dump["slowest"]
    assert slow["name"] == "t.request"
    assert slow["duration_ms"] >= 0
    assert slow["attrs"] == {"role": "test"}
    (span,) = slow["spans"]
    assert span["name"] == "stage_a"
    assert span["detail"] == 7


def test_nested_trace_reuses_outer_unless_fresh(recorder):
    with tracing.trace_request("outer") as outer:
        with tracing.trace_request("inner") as inner:
            assert inner is outer  # nested root reuses the active trace
        with tracing.trace_request(
            "server_side", trace_id=outer.trace_id, fresh=True
        ) as srv:
            assert srv is not outer
            assert srv.trace_id == outer.trace_id
            assert tracing.current_trace() is srv
        assert tracing.current_trace() is outer
    # The fresh server-side trace and the outer trace both recorded.
    assert recorder.dump()["recorded"] == 2


def test_errored_trace_lands_in_error_ring(recorder):
    with pytest.raises(ValueError, match="boom"):
        with tracing.trace_request("t.request"):
            raise ValueError("boom")
    dump = recorder.dump()
    assert not dump["slowest"]
    (err,) = dump["errors"]
    assert err["error"] == "ValueError: boom"


def _finished_trace(name, duration_ms):
    t = tracing.Trace(name)
    t.duration_ms = duration_ms
    return t


def test_flight_recorder_keeps_the_slowest_n():
    rec = tracing.FlightRecorder(max_slow=3, max_recent=2)
    for d in [5.0, 1.0, 3.0, 2.0, 4.0]:
        rec.record(_finished_trace(f"t{d}", d))
    dump = rec.dump()
    assert dump["recorded"] == 5
    assert [t["duration_ms"] for t in dump["slowest"]] == [5.0, 4.0, 3.0]
    assert len(dump["recent"]) == 2  # plain most-recent ring
    rec.clear()
    assert rec.dump() == {
        "recorded": 0, "slowest": [], "errors": [], "recent": [],
    }


def test_flight_recorder_disabled_is_noop():
    rec = tracing.FlightRecorder()
    rec.enabled = False
    rec.record(_finished_trace("t", 1.0))
    assert rec.dump()["recorded"] == 0


def test_add_span_from_another_thread(recorder):
    with tracing.trace_request("t") as trace:
        worker = threading.Thread(
            target=tracing.add_span, args=("cross_thread", 2.5, trace)
        )
        worker.start()
        worker.join()
    (slow,) = recorder.dump()["slowest"]
    assert [s["name"] for s in slow["spans"]] == ["cross_thread"]


def test_stage_summary_aggregates_spans():
    tracing.reset_stages()
    for _ in range(3):
        with tracing.span("agg_stage"):
            pass
    summary = tracing.stage_summary()["agg_stage"]
    assert summary["count"] == 3
    assert summary["total_ms"] >= 0
    assert set(summary) >= {"mean_ms", "p50_ms", "p95_ms", "max_ms"}
    tracing.reset_stages()
    assert "agg_stage" not in tracing.stage_summary()


def test_counter_group():
    group = tracing.CounterGroup()
    group.inc("a")
    group.inc("a", 4)
    group.inc("b")
    assert group.get("a") == 5
    assert group.export() == {"a": 5, "b": 1}
    group.reset()
    assert group.export() == {}


# ---------------------------------------------------------------------------
# Metrics labels and histogram export
# ---------------------------------------------------------------------------


def test_labeled_name_convention():
    assert labeled_name("req") == "req"
    assert labeled_name("req", {"role": "leader", "b": 1}) == (
        "req{b=1,role=leader}"  # keys sorted -> stable instrument name
    )
    with pytest.raises(ValueError, match="reserved"):
        labeled_name("req", {"role": "a,b"})
    with pytest.raises(ValueError, match="reserved"):
        labeled_name("req", {"k=v": "x"})


def test_registry_labels_create_distinct_instruments():
    reg = MetricsRegistry()
    reg.counter("req", labels={"role": "leader"}).inc(2)
    reg.counter("req", labels={"role": "helper"}).inc()
    reg.histogram("lat_ms", labels={"role": "leader"}).observe(1.0)
    export = reg.export()
    assert export["counters"]["req{role=leader}"] == 2
    assert export["counters"]["req{role=helper}"] == 1
    assert export["histograms"]["lat_ms{role=leader}"]["count"] == 1


def test_histogram_export_percentiles_consistent():
    reg = MetricsRegistry()
    hist = reg.histogram("h", buckets=(10.0, 50.0))
    for v in range(1, 101):
        hist.observe(float(v))
    out = hist.export()
    assert out["count"] == 100
    assert out["sum"] == 5050.0
    # Nearest-rank on the sorted reservoir: round(0.5 * 99) = 50 -> 51.0.
    assert out["p50"] == 51.0
    assert out["p95"] == 95.0
    assert out["max"] == 100.0
    assert out["buckets"] == {"10.0": 10, "50.0": 40, "+inf": 50}
    assert hist.percentile(99) == 99.0


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def test_parse_labeled_name():
    assert exposition.parse_labeled_name("req") == ("req", {})
    assert exposition.parse_labeled_name("req{role=leader,lvl=2}") == (
        "req", {"role": "leader", "lvl": "2"}
    )
    # Malformed label bodies degrade instead of raising.
    base, labels = exposition.parse_labeled_name("req{oops}")
    assert labels == {} and "{" not in base


def test_render_prometheus_counters_and_gauges():
    text = exposition.render_prometheus({
        "counters": {"a.b": 2, "req{role=leader}": 1},
        "gauges": {"depth": 1.5},
        "histograms": {},
    })
    lines = text.splitlines()
    assert "# TYPE dpf_a_b counter" in lines
    assert "dpf_a_b 2" in lines
    assert "# TYPE dpf_req counter" in lines
    assert 'dpf_req{role="leader"} 1' in lines
    assert "# TYPE dpf_depth gauge" in lines
    assert "dpf_depth 1.5" in lines


def test_render_prometheus_histogram_buckets_cumulative():
    text = exposition.render_prometheus({
        "counters": {},
        "gauges": {},
        "histograms": {
            "lat": {
                "count": 3,
                "sum": 7.0,
                "buckets": {"1.0": 1, "2.0": 1, "+inf": 1},
            }
        },
    })
    lines = text.splitlines()
    assert "# TYPE dpf_lat histogram" in lines
    # Per-bucket increments re-accumulate to cumulative counts.
    assert 'dpf_lat_bucket{le="1"} 1' in lines
    assert 'dpf_lat_bucket{le="2"} 2' in lines
    assert 'dpf_lat_bucket{le="+Inf"} 3' in lines
    assert "dpf_lat_sum 7" in lines
    assert "dpf_lat_count 3" in lines
    # The +Inf bucket is last of the bucket series (cumulativity holds).
    buckets = [ln for ln in lines if "_bucket" in ln]
    assert buckets[-1] == 'dpf_lat_bucket{le="+Inf"} 3'


def test_render_prometheus_escapes_label_values():
    text = exposition.render_prometheus(
        {"counters": {'x{k=he"y}': 1}, "gauges": {}, "histograms": {}}
    )
    assert 'dpf_x{k="he\\"y"} 1' in text


def test_histogram_records_exemplar_inside_active_trace(recorder):
    reg = MetricsRegistry()
    with tracing.trace_request("req", recorder=recorder) as trace:
        reg.histogram("lat_ms").observe(42.0)
    export = reg.export()["histograms"]["lat_ms"]
    (bucket,) = export["exemplars"].keys()
    exemplar = export["exemplars"][bucket]
    assert exemplar["value"] == 42.0
    assert exemplar["trace_id"] == trace.trace_id
    assert float(bucket) >= 42.0  # lands on its own bucket bound


def test_histogram_without_trace_has_no_exemplars():
    reg = MetricsRegistry()
    reg.histogram("lat_ms").observe(42.0)
    assert "exemplars" not in reg.export()["histograms"]["lat_ms"]


def test_render_prometheus_exemplar_on_bucket_line(recorder):
    reg = MetricsRegistry()
    with tracing.trace_request("req", recorder=recorder) as trace:
        reg.histogram("lat_ms", buckets=(50.0, 100.0)).observe(42.0)
    reg.histogram("lat_ms", buckets=(50.0, 100.0)).observe(60.0)
    text = exposition.render_prometheus(reg.export())
    exemplar_lines = [
        ln for ln in text.splitlines() if "# {trace_id=" in ln
    ]
    (line,) = exemplar_lines  # only the traced bucket carries one
    assert line.startswith('dpf_lat_ms_bucket{le="50"}')
    assert f'# {{trace_id="{trace.trace_id}"}} 42' in line


# ---------------------------------------------------------------------------
# Trace-context envelope codec
# ---------------------------------------------------------------------------


def test_envelope_request_roundtrip_and_bare_passthrough():
    tid = tracing.new_trace_id()
    wrapped = propagation.encode_request(tid, b"inner-proto")
    assert propagation.try_decode_request(wrapped) == (tid, b"inner-proto")
    # 0xFF first byte: an old peer's proto parser rejects this payload.
    assert wrapped[0] == 0xFF
    # Bare payloads (old-version peers) pass through untouched.
    assert propagation.try_decode_request(b"\x0abare") == (None, b"\x0abare")
    with pytest.raises(propagation.EnvelopeError, match="body"):
        propagation.try_decode_request(wrapped + b"extra")


def test_envelope_response_roundtrip():
    tid = tracing.new_trace_id()
    spans = [{"name": "device_compute", "duration_ms": 1.25, "extra": "x"}]
    wrapped = propagation.encode_response(
        b"reply", tid, server_ms=3.5, spans=spans
    )
    meta, inner = propagation.try_decode_response(wrapped)
    assert inner == b"reply"
    assert meta["trace_id"] == tid
    assert meta["server_ms"] == 3.5
    assert meta["spans"] == [
        {"name": "device_compute", "duration_ms": 1.25}
    ]
    assert propagation.try_decode_response(b"bare") == (None, b"bare")


def test_envelope_v2_digest_rides_only_v2():
    """The critical-path digest (phases + recv/send timestamps +
    per-span offsets) is a v2-only extension: the identical encode call
    at v1 is byte-equal to the pre-digest encoder, so the downgrade
    ladder drops the digest and nothing else."""
    tid = tracing.new_trace_id()
    spans = [
        {"name": "device_compute", "duration_ms": 1.0, "offset_ms": 0.5}
    ]
    digest = dict(
        phases={"device_compute": 1.0, "respond": 0.25},
        recv_ms=10.0,
        send_ms=12.0,
    )
    meta, inner = propagation.try_decode_response(
        propagation.encode_response(
            b"r", tid, server_ms=2.0, spans=spans, **digest
        )
    )
    assert inner == b"r"
    assert meta["phases"] == {"device_compute": 1.0, "respond": 0.25}
    assert (meta["recv_ms"], meta["send_ms"]) == (10.0, 12.0)
    assert meta["spans"][0]["offset_ms"] == 0.5
    v1 = propagation.encode_response(
        b"r", tid, server_ms=2.0, spans=spans, version=1, **digest
    )
    assert v1 == propagation.encode_response(
        b"r", tid, server_ms=2.0, spans=spans, version=1
    )
    meta1, inner1 = propagation.try_decode_response(v1)
    assert inner1 == b"r"  # the inner share is never the casualty
    assert "phases" not in meta1 and "recv_ms" not in meta1
    assert meta1["server_ms"] == 2.0
    assert meta1["spans"] == [
        {"name": "device_compute", "duration_ms": 1.0}
    ]


def test_envelope_response_span_list_is_bounded():
    tid = tracing.new_trace_id()
    cap = propagation.MAX_RESPONSE_SPANS
    spans = [
        {"name": f"s{i}", "duration_ms": 1.0} for i in range(cap + 9)
    ]
    before = tracing.runtime_counters.export().get(
        "propagation.spans_dropped", 0
    )
    meta, _ = propagation.try_decode_response(
        propagation.encode_response(b"x", tid, server_ms=0.0, spans=spans)
    )
    assert len(meta["spans"]) == cap
    assert meta["spans"][0]["name"] == "s0"  # chronological head kept
    after = tracing.runtime_counters.export()["propagation.spans_dropped"]
    assert after - before == 9


def test_add_span_clamps_negative_offset(recorder):
    with tracing.trace_request("t.clamp") as trace:
        trace.add_span("rewound", 1.0, offset_ms=-5.0)
        trace.add_span("normal", 1.0, offset_ms=2.0)
        trace.add_remote_spans(
            [{"name": "early", "duration_ms": 0.5, "offset_ms": 1.0}],
            prefix="helper.",
            base_offset_ms=-3.0,
        )
        spans = trace.span_list()
    rewound = next(s for s in spans if s["name"] == "rewound")
    assert rewound["offset_ms"] == 0.0 and rewound["clamped"] is True
    normal = next(s for s in spans if s["name"] == "normal")
    assert normal["offset_ms"] == 2.0 and "clamped" not in normal
    early = next(s for s in spans if s["name"] == "helper.early")
    assert early["offset_ms"] == 0.0 and early["clamped"] is True


# ---------------------------------------------------------------------------
# Admin endpoint
# ---------------------------------------------------------------------------


def test_admin_endpoints_serve_metrics_varz_tracez(recorder):
    reg = MetricsRegistry()
    reg.counter("admin.hits", labels={"role": "leader"}).inc(3)
    with tracing.trace_request("admin.request"):
        with tracing.span("device_compute") as timing:
            pass
        reg.histogram("admin.request_ms").observe(timing.ms)
    tracing.runtime_counters.inc("pir.plan.materialized")
    try:
        with AdminServer(registry=reg, recorder=recorder) as admin:
            base = f"http://127.0.0.1:{admin.port}"

            assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"

            resp = urllib.request.urlopen(base + "/metrics")
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4"
            )
            text = resp.read().decode()
            assert 'dpf_admin_hits{role="leader"} 3' in text
            assert "# TYPE dpf_admin_request_ms histogram" in text
            assert "dpf_admin_request_ms_count 1" in text
            # Runtime counters (layers below serving) merge in too.
            assert "dpf_pir_plan_materialized" in text

            varz = json.load(urllib.request.urlopen(base + "/varz"))
            assert varz["metrics"]["counters"]["admin.hits{role=leader}"] == 3
            assert "device_compute" in varz["stages"]
            assert varz["uptime_s"] >= 0

            tracez = json.load(urllib.request.urlopen(base + "/tracez"))
            assert tracez["recorded"] == 1
            assert tracez["slowest"][0]["name"] == "admin.request"
            names = [s["name"] for s in tracez["slowest"][0]["spans"]]
            assert "device_compute" in names

            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + "/nope")
            assert e.value.code == 404
    finally:
        tracing.runtime_counters.reset()


# ---------------------------------------------------------------------------
# Serving Leader/Helper: trace propagation and envelope interop
# ---------------------------------------------------------------------------


def _assert_leader_trace_decomposed(dump):
    """The acceptance-criterion shape: one Leader trace whose spans
    split latency into queue wait / device compute / helper leg, with
    the Helper's server-side spans grafted on under `helper.`."""
    traces = dump["slowest"] + dump["recent"]
    leader = next(t for t in traces if t["name"] == "leader.request")
    names = [s["name"] for s in leader["spans"]]
    assert "queue_wait" in names
    assert "device_compute" in names
    assert "leader_own_share" in names
    assert "helper_leg" in names
    helper_leg = next(
        s for s in leader["spans"] if s["name"] == "helper_leg"
    )
    # Helper-reported compute vs. the rest of the RTT (the network).
    assert "remote_ms" in helper_leg and "network_ms" in helper_leg
    remote = [n for n in names if n.startswith("helper.")]
    assert "helper.device_compute" in remote
    # The Helper's own server-side trace shares the Leader's trace id:
    # pick the Helper trace of this very request, not the slowest one
    # (with several requests served, those come from different ones).
    helper_ids = {
        t["trace_id"] for t in traces if t["name"] == "helper.request"
    }
    assert leader["trace_id"] in helper_ids
    return leader


def test_trace_propagates_in_process(recorder):
    leader, helper = leader_helper_pair(InProcessTransport)
    with helper, leader:
        got = run_query(leader, [3, 99])
    assert got == [RECORDS[3], RECORDS[99]]
    assert leader._peer_envelope is True
    _assert_leader_trace_decomposed(recorder.dump())
    assert leader.metrics.export()["counters"]["leader.wire_downgrades"] == 0
    assert (
        leader.metrics.export()["histograms"]["leader.helper_remote_ms"][
            "count"
        ]
        == 1
    )


def test_span_log_covers_each_layer_of_a_served_request(recorder):
    """With the span log on, one two-party request logs every layer
    boundary, and the in-process Helper's root nests under the Leader's
    helper leg with the Leader's trace id."""
    leader, helper = leader_helper_pair(InProcessTransport)
    tracing.span_log.drain()
    with helper, leader:
        run_query(leader, [3])  # warm: compiles stay out of the log
        tracing.span_log.start()
        try:
            assert run_query(leader, [5]) == [RECORDS[5]]
        finally:
            records = tracing.span_log.drain()["records"]
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r)
    for name in (
        "leader.request", "helper.request", "helper_leg", "leader_own_share",
        "decode", "encode", "helper_decrypt", "mask", "combine",
        "batcher.submit", "batcher.queue_wait", "batcher.evaluate",
        "pir.stage_keys.host", "pir.stage_keys.put", "pir.inner_product",
        "pir.readback",
    ):
        assert name in by_name, name
    (root,) = by_name["leader.request"]
    (helper_root,) = by_name["helper.request"]
    assert helper_root["parent"] == "helper_leg"
    assert helper_root["trace_id"] == root["trace_id"]
    assert {r["attrs"]["party"] for r in by_name["batcher.evaluate"]} == {
        "leader", "helper"
    }
    for r in records:
        assert root["start_ns"] <= r["start_ns"] <= r["end_ns"] <= root["end_ns"]


def test_request_ms_and_the_waterfall_share_one_timing(recorder):
    from distributed_point_functions_tpu.observability import phases

    prev = phases.default_phase_recorder()
    waterfall = phases.set_default_phase_recorder(phases.PhaseRecorder())
    try:
        leader, helper = leader_helper_pair(InProcessTransport)
        with helper, leader:
            run_query(leader, [9])
        totals = {
            role: entry["end_to_end_ms"]["total_ms"]
            for role, entry in waterfall.waterfall().items()
        }
        hist = {
            role: session.metrics.export()["histograms"][
                f"{role}.request_ms"]["sum"]
            for role, session in (("leader", leader), ("helper", helper))
        }
    finally:
        phases.set_default_phase_recorder(prev)
    assert totals["leader"] == pytest.approx(hist["leader"], abs=1e-3)
    # The Helper's record is rooted by its wire entry point, so its
    # total also holds the wire decode and encode around the request.
    assert totals["helper"] > hist["helper"]


def test_trace_propagates_over_tcp(recorder):
    helper = HelperSession(DATABASE, encrypt_decrypt.decrypt, make_config())
    server = FramedTcpServer(
        helper.handle_wire, port=0, name="obs-helper"
    ).start()
    transport = TcpTransport("localhost", server.port)
    leader = LeaderSession(DATABASE, transport, make_config())
    try:
        with helper, leader:
            got = run_query(leader, [7, 42])
    finally:
        transport.close()
        server.stop()
    assert got == [RECORDS[7], RECORDS[42]]
    assert leader._peer_envelope is True
    leader_trace = _assert_leader_trace_decomposed(recorder.dump())
    assert leader_trace["duration_ms"] > 0


def test_old_helper_downgrades_leader_to_bare_proto(recorder):
    helper = HelperSession(DATABASE, encrypt_decrypt.decrypt, make_config())

    def old_helper(data):
        # An old-version Helper proto-parses the payload directly; the
        # envelope's 0xFF lead byte makes that fail before any handling.
        pir_pb2.PirRequest.FromString(data)
        return helper.handle_wire(data)

    leader = LeaderSession(
        DATABASE, InProcessTransport(old_helper), make_config()
    )
    with helper, leader:
        got = run_query(leader, [5, 64])
        # A second query must go out bare immediately (downgrade sticks).
        got2 = run_query(leader, [6])
        counters = leader.metrics.export()["counters"]
    assert got == [RECORDS[5], RECORDS[64]]
    assert got2 == [RECORDS[6]]
    assert leader._peer_envelope is False
    # Stepwise ladder: v3 -> v2 -> v1 -> bare, one downgrade per fault.
    assert counters["leader.wire_downgrades"] == 3
    # The probe faults did not consume a retry attempt.
    assert counters["leader.helper_retries"] == 0
    assert counters["leader.helper_failures"] == 0


def _v1_envelope_only(handler):
    """Wrap a Helper handler as a v1-envelope-era peer: v2 requests are
    rejected the way an old build would (envelope magic known, version
    byte not), bare and v1 traffic passes through."""

    def guard(payload):
        if payload.startswith(b"\xffDPT") and payload[4] != 1:
            raise propagation.EnvelopeError(
                f"unsupported envelope version {payload[4]}"
            )
        return handler(payload)

    return guard


def test_new_leader_steps_down_to_v1_helper_keeping_spans(recorder):
    """Decode matrix, new Leader x old (v1-envelope) Helper: two ladder
    steps (v3 -> v2 -> v1), and the downgrade drops only the generation
    echo and the digest — the inner share, server_ms split, and remote
    spans all survive at v1."""
    helper = HelperSession(DATABASE, encrypt_decrypt.decrypt, make_config())
    leader = LeaderSession(
        DATABASE,
        InProcessTransport(_v1_envelope_only(helper.handle_wire)),
        make_config(),
    )
    with helper, leader:
        got = run_query(leader, [5, 64])
        got2 = run_query(leader, [6])
        counters = leader.metrics.export()["counters"]
    assert got == [RECORDS[5], RECORDS[64]]
    assert got2 == [RECORDS[6]]
    assert leader._peer_envelope is True  # still an enveloped peer
    assert leader._peer_wire_version == 1  # ...pinned at v1, sticky
    assert counters["leader.wire_downgrades"] == 2
    assert counters["leader.helper_retries"] == 0
    # v1 keeps server_ms + spans, so the remote/network split and the
    # grafted helper.* spans are intact.
    leader_trace = _assert_leader_trace_decomposed(recorder.dump())
    helper_leg = next(
        s for s in leader_trace["spans"] if s["name"] == "helper_leg"
    )
    # ...but the digest is gone: no skew estimate on the leg.
    assert "offset_ms_est" not in helper_leg


def test_new_helper_answers_v1_requests_in_v1(recorder):
    """Decode matrix, old (v1) Leader x new Helper: the Helper answers
    in the request's version, so a v1 peer never sees v2 fields."""
    helper = HelperSession(DATABASE, encrypt_decrypt.decrypt, make_config())
    replies = []

    def capture(payload):
        out = helper.handle_wire(payload)
        replies.append(out)
        return out

    # helper_digest=False pins this Leader's envelope at v1 — from the
    # Helper's side it is indistinguishable from an old build.
    leader = LeaderSession(
        DATABASE,
        InProcessTransport(capture),
        make_config(helper_digest=False),
    )
    with helper, leader:
        got = run_query(leader, [8])
    assert got == [RECORDS[8]]
    assert leader.metrics.export()["counters"]["leader.wire_downgrades"] == 0
    assert replies and replies[-1][4] == 1  # version byte: answered v1
    meta, inner = propagation.try_decode_response(replies[-1])
    assert inner  # the share rode along
    assert meta["server_ms"] >= 0.0 and meta["spans"]
    assert "phases" not in meta
    assert "recv_ms" not in meta and "send_ms" not in meta


def test_new_leader_serves_old_bare_proto_clients(recorder):
    """An old client speaks bare proto to `handle_wire`; the reply must
    come back bare (no envelope magic) and parse as a plain proto."""
    leader, helper = leader_helper_pair(InProcessTransport)
    client = DenseDpfPirClient.create(NUM_RECORDS, encrypt_decrypt.encrypt)
    request, state = client.create_request([11])
    wire = serialization.pir_request_to_proto(
        client.dpf, request
    ).SerializeToString()
    with helper, leader:
        reply = leader.handle_wire(wire)
    assert not reply.startswith(b"\xffDPT")
    response = serialization.pir_response_from_proto(
        pir_pb2.PirResponse.FromString(reply)
    )
    assert client.handle_response(response, state) == [RECORDS[11]]


# ---------------------------------------------------------------------------
# Heavy-hitters wire v2: codec, propagation, and v1 interop
# ---------------------------------------------------------------------------

HH_CONFIG = hh.HeavyHittersConfig(domain_bits=8, level_bits=4, threshold=2)
HH_VALUES = [3, 3, 3, 77, 77, 200, 9, 9, 14]


@pytest.fixture(scope="module")
def hh_keys():
    client = hh.HeavyHittersClient(HH_CONFIG)
    pairs = [client.generate_report(v) for v in HH_VALUES]
    return [p[0] for p in pairs], [p[1] for p in pairs]


def test_hh_wire_v2_codec_roundtrip():
    frontier = np.array([0, 5, 1 << 40], dtype=np.uint64)
    tid = tracing.new_trace_id()
    req = hh.encode_eval_request(3, frontier, trace_id=tid, version=2)
    r, decoded, version, got_tid = hh.decode_eval_request_full(req)
    assert (r, version, got_tid) == (3, 2, tid)
    np.testing.assert_array_equal(decoded, frontier)
    # No trace id -> zeros on the wire -> None on decode.
    _, _, _, none_tid = hh.decode_eval_request_full(
        hh.encode_eval_request(3, frontier, version=2)
    )
    assert none_tid is None

    shares = np.array([7, 0, 0xFFFFFFFF], dtype=np.uint32)
    resp = hh.encode_eval_response(3, shares, helper_ms=12.5, version=2)
    (
        r,
        decoded,
        version,
        helper_ms,
        epoch,
        timing,
    ) = hh.decode_eval_response_full(resp)
    assert (r, version, helper_ms, epoch, timing) == (3, 2, 12.5, None, None)
    np.testing.assert_array_equal(decoded, shares)

    # The 2-tuple decoders keep working for every version.
    assert hh.decode_eval_response(resp)[0] == 3
    v1_req = hh.encode_eval_request(1, frontier, version=1)
    r, decoded = hh.decode_eval_request(v1_req)
    assert r == 1
    np.testing.assert_array_equal(decoded, frontier)
    # v1 requests carry no extension: 8 bytes shorter than v2.
    assert len(v1_req) + 8 == len(
        hh.encode_eval_request(1, frontier, version=2)
    )

    with pytest.raises(hh.ProtocolError, match="v2 extension"):
        hh.decode_eval_request_full(req[:20])
    with pytest.raises(ValueError, match="wire version"):
        hh.encode_eval_request(0, frontier, version=5)


def _hh_oracle():
    return hh.plaintext_heavy_hitters(HH_VALUES, HH_CONFIG)


def test_hh_v2_sweep_propagates_trace_and_helper_timing(recorder, hh_keys):
    keys0, keys1 = hh_keys
    helper = hh.HeavyHittersHelper(hh.HeavyHittersServer(HH_CONFIG, keys1))
    leader = hh.HeavyHittersLeader(
        hh.HeavyHittersServer(HH_CONFIG, keys0),
        InProcessTransport(helper.handle_wire),
    )
    result = leader.run()
    assert result.as_dict() == _hh_oracle()
    assert leader.wire_version == 4
    snap = leader.metrics.export()
    assert snap["counters"]["hh.wire_downgrades"] == 0
    rounds = snap["counters"]["hh.rounds"]
    assert snap["histograms"]["hh.helper_remote_ms"]["count"] == rounds
    assert snap["histograms"]["hh.helper_network_ms"]["count"] == rounds

    dump = recorder.dump()
    traces = dump["slowest"] + dump["recent"]
    sweep = next(t for t in traces if t["name"] == "hh.sweep")
    legs = [s for s in sweep["spans"] if s["name"] == "helper_leg"]
    assert len(legs) == rounds
    assert all("remote_ms" in s and "network_ms" in s for s in legs)
    assert any(s["name"] == "leader_own_share" for s in sweep["spans"])
    # Each Helper round rooted a server-side trace under the sweep's id
    # (count in the recent ring only — slow traces appear in both lists).
    helper_rounds = [
        t for t in dump["recent"] if t["name"] == "hh.helper.round"
    ]
    assert len(helper_rounds) == rounds
    assert all(t["trace_id"] == sweep["trace_id"] for t in helper_rounds)


def _v1_only(handler):
    """Wrap a Helper handler as a v1-only peer: any v2 message is
    rejected the way an old build would (before reaching the server)."""

    def guard(payload):
        if len(payload) >= 5 and payload[4] != 1:
            raise hh.ProtocolError(
                f"unsupported wire version {payload[4]}"
            )
        return handler(payload)

    return guard


def test_hh_leader_downgrades_for_v1_helper_in_process(hh_keys):
    keys0, keys1 = hh_keys
    helper = hh.HeavyHittersHelper(hh.HeavyHittersServer(HH_CONFIG, keys1))
    leader = hh.HeavyHittersLeader(
        hh.HeavyHittersServer(HH_CONFIG, keys0),
        InProcessTransport(_v1_only(helper.handle_wire)),
    )
    result = leader.run()
    assert result.as_dict() == _hh_oracle()
    assert leader.wire_version == 1
    # Stepwise: v4 -> v3 -> v2 -> v1, one downgrade per rejected probe.
    assert leader.metrics.export()["counters"]["hh.wire_downgrades"] == 3
    # v1 responses carry no helper timing, so no remote/network split.
    assert "hh.helper_remote_ms" not in leader.metrics.export()["histograms"]


def test_hh_leader_downgrades_for_v1_helper_over_tcp(hh_keys):
    keys0, keys1 = hh_keys
    helper = hh.HeavyHittersHelper(hh.HeavyHittersServer(HH_CONFIG, keys1))
    server = FramedTcpServer(
        _v1_only(helper.handle_wire), port=0, name="hh-v1-helper"
    ).start()
    transport = TcpTransport("localhost", server.port)
    leader = hh.HeavyHittersLeader(
        hh.HeavyHittersServer(HH_CONFIG, keys0), transport
    )
    try:
        # Over TCP the v1 peer's rejection surfaces as a dropped
        # connection (TransportError), the other downgrade trigger.
        result = leader.run()
    finally:
        transport.close()
        server.stop()
    assert result.as_dict() == _hh_oracle()
    assert leader.wire_version == 1
    assert leader.metrics.export()["counters"]["hh.wire_downgrades"] == 3


def test_hh_helper_answers_v1_leaders_in_v1(hh_keys):
    _, keys1 = hh_keys
    helper = hh.HeavyHittersHelper(hh.HeavyHittersServer(HH_CONFIG, keys1))
    frontier = np.arange(16, dtype=np.uint64)
    reply = helper.handle_wire(
        hh.encode_eval_request(0, frontier, version=1)
    )
    assert reply[4] == 1  # version byte: the Helper answered in v1
    (
        r,
        shares,
        version,
        helper_ms,
        epoch,
        timing,
    ) = hh.decode_eval_response_full(reply)
    assert (r, version, helper_ms, epoch, timing) == (0, 1, None, None, None)
    assert shares.shape == (16,)


def test_statusz_renders_circuit_breaker_rows():
    from distributed_point_functions_tpu.observability.admin import (
        AdminServer,
    )
    from distributed_point_functions_tpu.robustness import CircuitBreaker

    breaker = CircuitBreaker(
        failure_threshold=1, reset_timeout_ms=60_000.0, name="leader.helper"
    )
    breaker.record_failure()  # drive it open for the breach styling
    assert breaker.state == "open"

    class SessionShim:
        # Mirrors LeaderSession.breaker_export(): breaker export plus
        # the degraded-mode flag the /statusz row shows alongside it.
        def export(self):
            out = breaker.export()
            out["degraded_mode"] = True
            return out

    with AdminServer(
        registry=MetricsRegistry(), breakers={"leader.helper": SessionShim()}
    ) as admin:
        base = f"http://127.0.0.1:{admin.port}"
        html = urllib.request.urlopen(base + "/statusz").read().decode()
        assert "Circuit breakers" in html
        assert "leader.helper" in html
        assert "open" in html

        state = json.load(
            urllib.request.urlopen(base + "/statusz?format=json")
        )
        row = state["breakers"]["leader.helper"]
        assert row["state"] == "open"
        assert row["state_code"] == 2
        assert row["degraded_mode"] is True


# ---------------------------------------------------------------------------
# Route table: the 404 index is generated, never hand-maintained
# ---------------------------------------------------------------------------


def test_404_endpoint_index_matches_dispatched_routes():
    with AdminServer(registry=MetricsRegistry()) as admin:
        base = f"http://127.0.0.1:{admin.port}"
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/definitely_not_a_route")
        assert e.value.code == 404
        body = e.value.read().decode()
        # The advertised index is generated from the same route table
        # `_route` dispatches on — exactly, in order.
        advertised = body.split("try ", 1)[1].split()
        assert advertised == list(admin.routes)
        assert "/utilz" in advertised and "/timeseriesz" in advertised
        # And every advertised path really dispatches: none of them
        # falls through to the unknown-endpoint reply (optional
        # surfaces may 404 with their own "not attached" message).
        for path in admin.routes:
            url = base + path
            if path == "/profilez":
                url += "?duration_ms=1"
            try:
                urllib.request.urlopen(url).read()
            except urllib.error.HTTPError as err:
                assert "unknown endpoint" not in err.read().decode(), path


def test_utilz_reports_live_closed_loop_duty_cycle():
    from distributed_point_functions_tpu.observability.utilization import (
        UtilizationTracker,
        default_utilization_tracker,
        set_default_utilization_tracker,
    )

    prev = default_utilization_tracker()
    tracker = set_default_utilization_tracker(
        UtilizationTracker(window_s=60.0)
    )
    try:
        leader, helper = leader_helper_pair(InProcessTransport)
        try:
            for i in range(6):
                values = run_query(leader, [i])
                assert values[0] == RECORDS[i]
        finally:
            leader.close()
            helper.close()
        with AdminServer(
            registry=leader.metrics, utilization=tracker
        ) as admin:
            base = f"http://127.0.0.1:{admin.port}"
            state = json.load(
                urllib.request.urlopen(base + "/utilz?format=json")
            )
        # The real batcher worker reported: evaluations became busy
        # time and the waits became typed bubbles whose causes sum to
        # the measured idle total.
        tracked = state["current"]
        totals = state["totals"]
        busy = totals["busy_s"] + tracked["busy_s"]
        idle = totals["idle_total_s"] + tracked["idle_total_s"]
        assert busy > 0.0
        assert idle > 0.0
        causes = dict(totals["idle_s"])
        for cause, s in tracked["idle_s"].items():
            causes[cause] = causes.get(cause, 0.0) + s
        # Causes sum to the measured idle total (within the export's
        # per-cause 6-decimal rounding).
        assert sum(causes.values()) == pytest.approx(idle, abs=1e-4)
        assert set(causes) <= {
            "empty_queue", "admission_shed", "batch_wait",
            "pipeline_full", "staging_sync", "helper_rtt",
            "snapshot_flip", "other",
        }
        # The helper leg reported its exposed RTT barrier.
        assert "leader" in state["threads"] or "helper_rtt" in causes
    finally:
        set_default_utilization_tracker(prev)
