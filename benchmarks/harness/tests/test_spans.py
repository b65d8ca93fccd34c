"""The scope reader, the span log on the trace's clock, and the span and
scope metrics, on traces with known answers: a hand-written XSpace with
program scopes (`data/scoped.xplane.pbtxt`) and the small trace
recorded on a v5e chip (`record_trace.py`)."""

import os
import time

import pytest

import cell
import spanlog
import trace_reduce
import xplane_scopes

from distributed_point_functions_tpu.observability import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "recorded_tpu.xplane.pb")
# Log time = trace time + 4 ms in the synthetic case.
SHIFT = 4_000_000
MARKS_NS = [1050 + SHIFT, 101050 + SHIFT]


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """The synthetic XSpace, serialized to a file as a run keeps it."""
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "scoped.xplane.pbtxt")) as f:
        data = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("xplane") / "scoped.xplane.pb"
    path.write_bytes(data)
    return str(path)


def span(name, start, end, parent=None, trace_id="t", thread=1, attrs=None):
    """A span record at trace times `start`-`end` (ns), on the log clock."""
    return {"name": name, "start_ns": start + SHIFT, "end_ns": end + SHIFT,
            "parent": parent, "trace_id": trace_id, "thread": thread,
            "attrs": attrs}


SPANS = [
    span("leader.request", 2000, 60000),
    span("mask", 4000, 12000),
    span("decode", 20000, 30000),
    span("decode", 300000, 301000),
]


def read(name, record):
    return cell.metric(name).read(record)


# -- scope reader -------------------------------------------------------------


def test_scope_reader_finds_the_recorded_programs():
    scopes = xplane_scopes.Scopes.from_file(RECORDED)
    totals = scopes.totals()
    assert totals["jit(xla_step)/dot_general"] > 0
    assert totals["jit(pallas_step)/pallas_call"] > 0
    assert scopes.scope_ns("dot_general") == totals["jit(xla_step)/dot_general"]


def test_scopes_by_string_and_by_reference(scoped):
    scopes = xplane_scopes.Scopes.from_file(scoped)
    window = spanlog.window({"xplane": scoped})
    assert window == (1000, 101100)
    assert scopes.scope_ns("pir.expand", window) == 6000
    assert scopes.scope_ns("dpf.leaf_values", window) == 6000
    assert scopes.scope_ns("dpf.leaf_values", (1000, 14000)) == 3000
    assert scopes.totals(window) == {
        "jit(run)/pir.expand": 6000, "jit(run)/dpf.leaf_values": 6000,
        "": 5000,
    }
    assert sum(b - a for a, b in scopes.gaps(window)) == 83100


def test_trace_reduce_outputs_on_the_recorded_trace_are_unchanged():
    reduction = trace_reduce.reduce_file(RECORDED)
    assert reduction.window_ns == 52799516.0
    assert reduction.busy_ns == 3613.0
    assert reduction.modules == {"jit_pallas_step(11925949139441790376)": 3618.0}
    assert [name for name, _ in reduction.top_gaps()] == [
        "host.sleep", "step.pallas"
    ]
    assert reduction.top_gaps()[0][1] == 0.050726803
    (op, seconds), = reduction.top_ops()
    assert op.startswith("%pallas_step.1 = ") and seconds == 3.613e-06


# -- clock --------------------------------------------------------------------


def test_marks_align_the_log_with_the_trace(scoped):
    clock = spanlog.clock({"xplane": scoped, "marks_ns": MARKS_NS})
    assert clock == {"offset_ns": -SHIFT, "drift_ns": 0}
    drifted = spanlog.clock(
        {"xplane": scoped, "marks_ns": [MARKS_NS[0], MARKS_NS[1] + 30]}
    )
    assert drifted["drift_ns"] == -30


def test_logged_spans_start_at_their_annotations(scoped):
    annotations = xplane_scopes.host_events(
        spanlog.profile({"xplane": scoped}), {"leader.request", "mask", "decode"}
    )
    # The last decode is 280 us from any decode annotation.
    assert xplane_scopes.annotation_agreement(SPANS, annotations, -SHIFT) == (
        3, 4
    )


def test_each_idle_gap_goes_to_the_innermost_span_over_it(scoped):
    scopes = xplane_scopes.Scopes.from_file(scoped)
    attributed, idle, by_name = xplane_scopes.attribute_idle(
        scopes.gaps((1000, 101100)), SPANS, -SHIFT
    )
    assert (attributed, idle) == (35000, 83100)
    assert by_name == {"mask": 6000, "decode": 14000,
                       "leader.request": 15000, None: 48100}


# -- metrics ------------------------------------------------------------------


def log_of(*records, dropped=0):
    return {"records": list(records), "dropped": dropped}


def test_span_metrics_read_the_whole_window_log():
    ms = 1_000_000
    record = {"spans": log_of(
        span("batcher.queue_wait", 0, 2 * ms),
        span("batcher.queue_wait", 0, 6 * ms, thread=2),
        span("batcher.queue_wait", 0, 4 * ms),
        span("pir.stage_keys.put", 0, 1 * ms),
        span("pir.stage_keys.put", 0, 1089 * ms),
        span("helper.request", 0, 50 * ms, trace_id="a"),
        span("batcher.submit", 5 * ms, 35 * ms, parent="helper.request",
             trace_id="a"),
        span("helper.request", 0, 40 * ms, trace_id="b"),
        span("batcher.submit", 5 * ms, 30 * ms, parent="helper.request",
             trace_id="b"),
        span("batcher.submit", 0, 9 * ms, parent="leader_own_share",
             trace_id="b"),
        span("dpf.evaluate_next", 0, 2 * ms),
        span("dpf.evaluate_next", 0, 3 * ms),
        span("dpf.evaluate_next", 0, 4 * ms),
    )}
    assert read("queue_wait_p50_ms.served", record) == 4.0
    assert read("key_staging_max_ms.served", record) == 1089.0
    assert read("helper_host_p50_ms.served", record) == 17.5
    assert read("host_dispatch_us_per_eval.dpf", record) == 3000.0


def test_a_log_with_drops_or_no_log_gives_nothing():
    record = {"spans": log_of(span("batcher.queue_wait", 0, 5), dropped=1)}
    assert read("queue_wait_p50_ms.served", record) is None
    assert read("queue_wait_p50_ms.served", {"trace": None}) is None


def test_without_a_drained_log_the_traced_span_of_the_live_log_is_read():
    tracing.span_log.drain()
    tracing.span_log.start()
    try:
        tracing.log_span("batcher.queue_wait", 0, 7_000_000)  # before
        t_start = time.perf_counter()
        now = time.perf_counter_ns()
        tracing.log_span("batcher.queue_wait", now - 3_000_000, now)
        t_stop = time.perf_counter()
        traced = {"t_start": t_start, "t_stop": t_stop}
        assert read("queue_wait_p50_ms.served",
                    {"trace": object(), "traced": traced}) == 3.0
        # No device trace was read: nothing to read the log beside.
        assert read("queue_wait_p50_ms.served",
                    {"trace": None, "traced": traced}) is None
    finally:
        tracing.span_log.drain()


def test_scope_metrics_over_the_traced_window(scoped):
    batchers = {"leader": {"requests_submitted": 3, "padded_keys": 1},
                "helper": {"requests_submitted": 3, "padded_keys": 1}}
    record = {"xplane": scoped, "traced": {"counters": batchers}}
    assert read("expand_device_us_per_key.served", record) == 6000 / 1e3 / 8
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "scoped.xplane.pbtxt")) as f:
        reduction = trace_reduce.reduce(ProfileData.from_text_proto(f.read()))
    assert reduction.busy_ns == 17000
    record = {"xplane": scoped, "trace": reduction}
    assert read("leaf_values_share_pct.dpf", record) == pytest.approx(
        100 * 6000 / 17000
    )


def test_idle_attributed_metric(scoped):
    record = {"xplane": scoped, "marks_ns": MARKS_NS, "spans": log_of(*SPANS)}
    assert read("idle_attributed_pct.served", record) == pytest.approx(
        100 * 35000 / 83100
    )
    # Without the marks the log cannot be placed on the trace.
    del record["marks_ns"]
    assert read("idle_attributed_pct.served", record) is None


def test_scope_metrics_need_the_raw_trace():
    for name in ("expand_device_us_per_key.served",
                 "leaf_values_share_pct.dpf", "idle_attributed_pct.served"):
        assert read(name, {"trace": None, "traced": {}}) is None
