"""The span log (`observability/tracing.py`): a bounded ring of span
records on one clock, off unless started or a profiler captures; the
one-timing rule for a phase bracket and a span over one interval; the
batcher's out-of-band spans; and the named scopes that the device
programs carry in their HLO metadata."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_point_functions_tpu import dpf as dpf_mod
from distributed_point_functions_tpu.dpf import (
    DistributedPointFunction,
    DpfParameters,
)
from distributed_point_functions_tpu.observability import phases, tracing
from distributed_point_functions_tpu.observability.device import (
    CompileTracker,
)
from distributed_point_functions_tpu.ops import aes
from distributed_point_functions_tpu.ops.inner_product_pallas import (
    _ip_pallas_staged_v2,
)
from distributed_point_functions_tpu.pir import dense_eval, dense_eval_planes
from distributed_point_functions_tpu.serving.batcher import DynamicBatcher
from distributed_point_functions_tpu.value_types import IntType


@pytest.fixture
def log():
    """The process-wide log, stopped and empty around each test."""
    tracing.span_log.drain()
    yield tracing.span_log
    tracing.span_log.drain()


def names(snapshot):
    return [r["name"] for r in snapshot["records"]]


def test_off_records_nothing(log):
    with tracing.trace_request("t.off"):
        with tracing.span("stage"):
            pass
        tracing.add_span("oob", 1.0)
        with phases.phase("respond"):
            pass
    assert not log.on
    assert log.snapshot()["records"] == []


def test_ring_is_bounded_and_counts_drops(monkeypatch):
    small = tracing.SpanLog(capacity=4)
    monkeypatch.setattr(tracing, "span_log", small)
    small.start()
    for i in range(10):
        with tracing.span(f"s{i}"):
            pass
    snap = small.drain()
    assert names(snap) == ["s6", "s7", "s8", "s9"]
    assert snap["dropped"] == 6 and snap["capacity"] == 4
    assert small.snapshot()["records"] == [] and not small.on
    assert tracing.SPAN_LOG_CAPACITY >= 1 << 17


def test_records_keep_parent_trace_and_clock(log):
    log.start()
    t_before = time.perf_counter_ns()
    with tracing.trace_request("t.root") as trace:
        with tracing.span("outer", bucket=4):
            with tracing.span("inner"):
                pass
            tracing.add_span("oob", 0.5)
    t_after = time.perf_counter_ns()
    records = {r["name"]: r for r in log.drain()["records"]}
    assert records["t.root"]["parent"] is None
    assert records["outer"]["parent"] == "t.root"
    assert records["inner"]["parent"] == "outer"
    assert records["outer"]["attrs"] == {"bucket": 4}
    for r in records.values():
        assert r["trace_id"] == trace.trace_id
        assert r["start_ns"] <= r["end_ns"] <= t_after
        # An out-of-band span's start is its end minus its duration.
        assert r["name"] == "oob" or t_before <= r["start_ns"]
    root = records["t.root"]
    assert (root["start_ns"], root["end_ns"]) == (trace.start_ns, trace.end_ns)
    assert records["oob"]["end_ns"] - records["oob"]["start_ns"] == 500_000


def test_profiler_session_turns_the_log_on(log, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("profiled"):
            pass
    finally:
        jax.profiler.stop_trace()
    with tracing.span("after"):
        pass
    assert names(log.snapshot()) == ["profiled"]


def test_phase_bracket_and_span_are_one_timing(log):
    log.start()
    recorder = phases.PhaseRecorder()
    with tracing.trace_request("t.phase") as trace:
        with recorder.request("leader") as req:
            with phases.phase("respond", span="decode", n=1) as timing:
                time.sleep(0.002)
            phase_ms = req.snapshot()["respond"]
    record = next(r for r in log.drain()["records"] if r["name"] == "decode")
    assert phase_ms == timing.ms
    assert (record["start_ns"], record["end_ns"]) == (
        timing.start_ns, timing.end_ns
    )
    span = next(s for s in trace.spans if s["name"] == "decode")
    assert span["duration_ms"] == round(timing.ms, 3) and span["n"] == 1


def test_bare_phase_is_logged_under_its_name(log):
    log.start()
    recorder = phases.PhaseRecorder()
    with recorder.request("helper"):
        with phases.phase("h2d_transfer") as timing:
            pass
    record = log.drain()["records"][0]
    assert record["name"] == "phase.h2d_transfer"
    assert record["end_ns"] - record["start_ns"] == timing.end_ns - timing.start_ns


def test_compile_tracker_takes_the_span_timing():
    tracker = CompileTracker()
    with tracker.dispatch("site", "k") as dispatch, \
            tracing.span("call", record=False) as timing:
        dispatch.timing = timing
        time.sleep(0.001)
    assert tracker.export()["sites"]["site"]["compile_ms"]["max"] == round(
        timing.ms, 3
    )


def test_batcher_spans_land_between_submit_and_answer(log):
    def evaluate(keys):
        time.sleep(0.002)
        return [k * 2 for k in keys]

    batcher = DynamicBatcher(
        evaluate, max_batch_size=4, max_wait_ms=1.0, name="leader.batcher"
    )
    try:
        log.start()
        t_submit = time.perf_counter_ns()
        with tracing.trace_request("leader.request") as trace:
            assert batcher.submit([1, 2, 3]) == [2, 4, 6]
        t_answer = time.perf_counter_ns()
    finally:
        batcher.close()
    records = log.drain()["records"]
    by_name = {r["name"]: r for r in records}
    submit = by_name["batcher.submit"]
    wait = by_name["batcher.queue_wait"]
    evaluate_span = by_name["batcher.evaluate"]
    assert (
        t_submit <= submit["start_ns"] <= wait["start_ns"] <= wait["end_ns"]
        <= evaluate_span["start_ns"] <= evaluate_span["end_ns"]
        <= submit["end_ns"] <= t_answer
    )
    assert wait["parent"] == "batcher.submit"
    assert submit["parent"] == "leader.request"
    assert wait["trace_id"] == trace.trace_id
    assert wait["attrs"] == {"party": "leader"}
    assert evaluate_span["attrs"] == {"bucket": 4, "keys": 3, "party": "leader"}
    hist = batcher.metrics.export()["histograms"]["leader.batcher.evaluate_ms"]
    assert hist["count"] == 1


# ---------------------------------------------------------------------------
# Named scopes in the device programs' HLO metadata (CPU lowering)
# ---------------------------------------------------------------------------


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _selection_args(nk, levels):
    return (_u32(nk, 4), _u32(nk), _u32(levels, nk, 4), _u32(levels, nk),
            _u32(levels, nk), _u32(nk, 4))


def _pir_expand_limb():
    return dense_eval.evaluate_selection_blocks.lower(
        *_selection_args(4, 3), walk_levels=1, expand_levels=2, num_blocks=4
    )


def _pir_expand_planes():
    return dense_eval_planes._evaluate_selection_blocks_planes_jit.lower(
        *_selection_args(32, 3), walk_levels=1, expand_levels=2, num_blocks=4
    )


def _pir_inner_product():
    return _ip_pallas_staged_v2.lower(
        _u32(32, 16, 4), _u32(8, 16), interpret=True
    )


def _full_domain_args():
    dpf = DistributedPointFunction.create(
        DpfParameters(log_domain_size=6, value_type=IntType(64))
    )
    key, _ = dpf.generate_keys(5, 7)
    stop = dpf._hierarchy_to_tree[0]
    seeds = jnp.asarray(aes.u128_to_limbs(key.seed))[None, :]
    control = jnp.asarray(np.array([key.party], dtype=np.uint32))
    return dpf, key, stop, seeds, control


def _dpf_expand():
    dpf, key, stop, seeds, control = _full_domain_args()
    run = dpf_mod._expand_levels_fn(stop, hash_leaves=True)
    return run.lower(seeds, control, *dpf._device_correction_words(key, 0, stop))


def _dpf_leaf_values():
    dpf, key, stop, seeds, control = _full_domain_args()
    run = dpf_mod._expand_levels_fn(stop, hash_leaves=True)
    leaves, ctrl = run(
        seeds, control, *dpf._device_correction_words(key, 0, stop)
    )
    return dpf_mod._leaf_stage.lower(
        leaves, ctrl, dpf._stage_value_correction(key, 0),
        vtype=dpf.parameters[0].value_type, cepb=1 << (6 - stop),
        num_blocks=dpf._blocks_needed[0], party=key.party, pre_hashed=True,
    )


@pytest.mark.parametrize("scope,lower", [
    ("pir.expand", _pir_expand_limb),
    ("pir.expand", _pir_expand_planes),
    ("pir.inner_product", _pir_inner_product),
    ("dpf.expand", _dpf_expand),
    ("dpf.leaf_values", _dpf_leaf_values),
], ids=["pir.expand-limb", "pir.expand-planes", "pir.inner_product",
        "dpf.expand", "dpf.leaf_values"])
def test_named_scope_in_lowered_metadata(scope, lower):
    text = lower().as_text(debug_info=True)
    assert f'/{scope}/' in text or f'"{scope}/' in text
