"""Capacity package units: byte/time model, calibration, admission.

Everything here is deterministic — models get pinned budgets and
calibration files, controllers get fake clocks — so the arithmetic the
planners and the serving admission path delegate to is checked exactly,
with no JAX and no wall clock.
"""

import json
import time

import pytest

from distributed_point_functions_tpu.capacity import (
    AdmissionController,
    BROWNOUT_STEPS,
    BrownoutController,
    CapacityModel,
    ShedReason,
    TenantPolicy,
    ThroughputCalibration,
    TokenBucket,
    WeightedFairQueue,
)
from distributed_point_functions_tpu.serving.metrics import MetricsRegistry

GIB = 1 << 30


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def pinned_model(tmp_path, qps=1000.0, lanes=1_000_000.0, **kwargs):
    """A CapacityModel calibrated from a throwaway history file, so
    device-ms pricing is exact (1 key == 1 ms at qps=1000)."""
    path = tmp_path / "history.jsonl"
    records = [
        {"metric": "serving_closed_loop_queries_per_sec", "value": qps},
        {"metric": "heavy_hitters_sweep_lanes_per_sec", "value": lanes},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    kwargs.setdefault("device_memory_bytes", 16 * GIB)
    return CapacityModel(
        calibration=ThroughputCalibration(str(path)), **kwargs
    )


# ---------------------------------------------------------------------------
# Byte model: the planner formulas, verbatim
# ---------------------------------------------------------------------------


def test_selection_byte_formulas():
    m = CapacityModel(device_memory_bytes=16 * GIB)
    assert m.materialized_selection_bytes(8, 256) == 8 * 256 * 16
    # cut-state + double-buffered chunk
    assert m.streaming_selection_bytes(8, 10, 6) == 8 * 16 * (
        (1 << 10) + 2 * (1 << 6)
    )
    assert m.chunked_selection_bytes(8, 10) == 8 * (1 << 10) * 16


def test_pick_streaming_split_prefers_largest_feasible():
    m = CapacityModel(device_memory_bytes=16 * GIB)
    expand = 20
    budget = m.selection_budget_bytes()
    split = m.pick_streaming_split(64, expand)
    assert (
        m.streaming_selection_bytes(64, expand - split, split) <= budget
    )
    if split < expand:
        assert (
            m.streaming_selection_bytes(
                64, expand - (split + 1), split + 1
            )
            > budget
        )


def test_pick_streaming_split_minimizes_peak_when_infeasible():
    m = CapacityModel(device_memory_bytes=16 * GIB, selection_budget=1)
    expand = 10
    split = m.pick_streaming_split(1 << 20, expand)
    best = min(
        m.streaming_selection_bytes(1 << 20, expand - r, r)
        for r in range(expand + 1)
    )
    assert (
        m.streaming_selection_bytes(1 << 20, expand - split, split) == best
    )


def test_pick_chunked_expand_levels_caps_at_granule_and_budget():
    m = CapacityModel(device_memory_bytes=16 * GIB)
    # Plenty of budget: the MXU granule is the cap.
    assert m.pick_chunked_expand_levels(1, 20, 10) == 10
    # Tight budget: shrink until one chunk fits (floor 0).
    tight = CapacityModel(
        device_memory_bytes=16 * GIB, selection_budget=1024
    )
    cel = tight.pick_chunked_expand_levels(4, 20, 10)
    assert tight.chunked_selection_bytes(4, cel) <= 1024 or cel == 0


def test_hh_level_plan_is_pow2_and_fits():
    m = CapacityModel(device_memory_bytes=16 * GIB, frontier_budget=1 << 20)
    plan = m.plan_hh_level(
        num_keys=100, num_prefixes=700, walk_levels=4, value_blocks=1
    )
    assert plan.lane_bytes == 16 * (4 + 1 + 3)
    assert plan.chunk_prefixes & (plan.chunk_prefixes - 1) == 0
    assert plan.bytes_peak == 100 * plan.chunk_prefixes * plan.lane_bytes
    assert plan.bytes_peak <= plan.budget_bytes or plan.chunk_prefixes == 1
    assert plan.num_chunks * plan.chunk_prefixes >= 700


# ---------------------------------------------------------------------------
# Budget resolution order: env > ctor > device fraction > default
# ---------------------------------------------------------------------------


def test_budget_resolution_order(monkeypatch):
    monkeypatch.delenv("DPF_TPU_SELECTION_BYTES_BUDGET", raising=False)
    monkeypatch.delenv("DPF_TPU_HH_BYTES_BUDGET", raising=False)
    # Known device memory: budgets derive as fractions; on a 16 GiB v5e
    # the derivation lands exactly on the historical fixed defaults.
    m = CapacityModel(device_memory_bytes=16 * GIB)
    assert m.selection_budget_bytes() == 1 * GIB
    assert m.frontier_budget_bytes() == 256 * (1 << 20)
    # Explicit construction beats the derivation.
    m2 = CapacityModel(
        device_memory_bytes=16 * GIB,
        selection_budget=123456,
        frontier_budget=7890,
    )
    assert m2.selection_budget_bytes() == 123456
    assert m2.frontier_budget_bytes() == 7890
    # Env beats everything.
    monkeypatch.setenv("DPF_TPU_SELECTION_BYTES_BUDGET", "999")
    monkeypatch.setenv("DPF_TPU_HH_BYTES_BUDGET", "888")
    assert m2.selection_budget_bytes() == 999
    assert m2.frontier_budget_bytes() == 888


def test_unknown_device_memory_keeps_historical_defaults(monkeypatch):
    monkeypatch.delenv("DPF_TPU_SELECTION_BYTES_BUDGET", raising=False)
    monkeypatch.delenv("DPF_TPU_HH_BYTES_BUDGET", raising=False)
    monkeypatch.setenv("DPF_TPU_DEVICE_MEMORY_BYTES", "")
    m = CapacityModel(calibration=ThroughputCalibration("/nonexistent"))
    if m.device_memory_bytes is None:  # CPU test process
        assert m.selection_budget_bytes() == 1 * GIB
        assert m.frontier_budget_bytes() == 256 * (1 << 20)


# ---------------------------------------------------------------------------
# Calibration: newest clean record wins, junk degrades to fallbacks
# ---------------------------------------------------------------------------


def test_calibration_newest_clean_record_wins(tmp_path):
    path = tmp_path / "h.jsonl"
    lines = [
        json.dumps({"metric": "m", "value": 100.0}),
        "not json at all",
        json.dumps({"metric": "m", "value": 0.0}),  # non-positive: dirty
        json.dumps({"metric": "m", "value": 250.0, "status": "ok"}),
        json.dumps({"metric": "m", "value": 999.0, "status": "regression"}),
    ]
    path.write_text("\n".join(lines) + "\n")
    cal = ThroughputCalibration(str(path))
    assert cal.lookup("m") == 250.0
    assert cal.lookup("absent") is None
    assert cal.throughput("absent", 7.0) == 7.0


def test_calibration_missing_file_degrades_to_fallback(tmp_path):
    cal = ThroughputCalibration(str(tmp_path / "never_written.jsonl"))
    m = CapacityModel(device_memory_bytes=16 * GIB, calibration=cal)
    # The built-in fallbacks are the derated v5e captures.
    assert m.serving_queries_per_sec() == 1300.0
    assert m.hh_lanes_per_sec() == 950_000.0


def _bench_record(metric, value, status="ok", ts=None, **extra):
    """One history.jsonl record in the real writer's shape (stack
    stamps and all) — harness-failure episodes mix ok,
    infra_error, and last_good rows exactly like this."""
    rec = {
        "device": extra.pop("device", "v5e-1"),
        "git_rev": extra.pop("git_rev", "abc1234"),
        "metric": metric,
        "status": status,
        "topology": extra.pop("topology", "1x1"),
        "ts_unix": time.time() if ts is None else ts,
        "unit": extra.pop("unit", "per_sec"),
        "value": value,
        "vs_baseline": extra.pop("vs_baseline", None),
    }
    rec.update(extra)
    return rec


def test_calibration_skips_infra_error_and_last_good(tmp_path):
    """The harness-failure shape: an infra_error record echoing the last
    good value, then explicit last_good echoes, must never calibrate —
    only genuinely clean rows do, and the skips are counted."""
    path = tmp_path / "h.jsonl"
    rows = [
        _bench_record("serving_closed_loop_queries_per_sec", 2600.0),
        _bench_record(
            "serving_closed_loop_queries_per_sec", 2590.0,
            status="infra_error", error="backend init failed",
            last_good=2600.0,
        ),
        _bench_record(
            "serving_closed_loop_queries_per_sec", 2600.0,
            status="last_good",
        ),
        _bench_record(
            "heavy_hitters_sweep_lanes_per_sec", 1.9e6,
            status="infra_error", error="tpu preempted",
        ),
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    cal = ThroughputCalibration(str(path))
    assert cal.lookup("serving_closed_loop_queries_per_sec") == 2600.0
    # The hh metric never had a clean row: fallback, not the echo.
    assert cal.lookup("heavy_hitters_sweep_lanes_per_sec") is None
    assert cal.throughput(
        "heavy_hitters_sweep_lanes_per_sec", 950_000.0
    ) == 950_000.0
    export = cal.export()
    assert export["skipped_records"] == {"infra_error": 2, "last_good": 1}


def test_calibration_mixed_stack_stamps_last_clean_wins(tmp_path):
    """Append order is time order whatever the (device, topology,
    git_rev) stamp: a newer clean record from a different stack stamp
    replaces the older one, per metric independently."""
    path = tmp_path / "h.jsonl"
    rows = [
        _bench_record(
            "serving_closed_loop_queries_per_sec", 1000.0,
            device="v5e-1", topology="1x1", git_rev="old1111",
        ),
        _bench_record(
            "heavy_hitters_sweep_lanes_per_sec", 2.0e6,
            device="v5e-1", topology="1x1",
        ),
        _bench_record(
            "serving_closed_loop_queries_per_sec", 2600.0,
            device="v5p-8", topology="2x4", git_rev="new2222",
        ),
        _bench_record(
            "serving_closed_loop_queries_per_sec", 0.0,  # dirty value
            device="v5p-8", topology="2x4",
        ),
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    cal = ThroughputCalibration(str(path))
    assert cal.lookup("serving_closed_loop_queries_per_sec") == 2600.0
    assert cal.lookup("heavy_hitters_sweep_lanes_per_sec") == 2.0e6


def test_calibration_staleness_and_record_age(tmp_path):
    path = tmp_path / "h.jsonl"
    now = time.time()
    rows = [
        _bench_record("fresh_metric", 100.0, ts=now - 10.0),
        _bench_record("old_metric", 200.0, ts=now - 500.0),
        {"metric": "untimed_metric", "value": 300.0, "status": "ok"},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    cal = ThroughputCalibration(str(path), stale_after_s=60.0)
    assert cal.record_age_s("fresh_metric") == pytest.approx(10.0, abs=5.0)
    assert not cal.stale("fresh_metric")
    assert cal.stale("old_metric")
    # A clean record without a timestamp can't be aged: fresh, not
    # permanently stale.
    assert cal.record_age_s("untimed_metric") is None
    assert not cal.stale("untimed_metric")
    # No record at all IS stale (pricing runs on fallbacks).
    assert cal.stale("absent_metric")
    export = cal.export()
    assert export["stale"] is True  # old_metric drags the summary flag
    assert export["metrics"]["fresh_metric"]["stale"] is False
    assert export["metrics"]["old_metric"]["stale"] is True
    assert export["metrics"]["old_metric"]["age_s"] == pytest.approx(
        500.0, abs=5.0
    )
    assert export["stale_after_s"] == 60.0


def test_calibration_fallback_journals_once_per_metric(tmp_path):
    from distributed_point_functions_tpu.observability.events import (
        default_journal,
    )

    journal = default_journal()
    seq0 = max((e["seq"] for e in journal.tail(n=1)), default=0)
    cal = ThroughputCalibration(str(tmp_path / "missing.jsonl"))
    for _ in range(3):
        assert cal.throughput("test_only_fallback_metric", 7.0) == 7.0
    events = [
        e
        for e in journal.tail(n=32, kind="capacity.calibration_fallback")
        if e["seq"] > seq0
        and e.get("metric") == "test_only_fallback_metric"
    ]
    assert len(events) == 1
    assert events[0]["fallback"] == 7.0


def test_price_pir_keys_device_ms(tmp_path):
    m = pinned_model(tmp_path, qps=1000.0)
    cost = m.price_pir_keys(5)
    assert cost.device_ms == pytest.approx(5.0)  # 1 key == 1 ms
    assert cost.quantity == 5 and cost.unit == "pir_keys"
    assert m.price_pir_keys(5, num_blocks=64).bytes_peak == 5 * 64 * 16


def test_price_hh_level(tmp_path):
    m = pinned_model(tmp_path, lanes=1_000_000.0)
    cost = m.price_hh_level(
        num_keys=100, num_prefixes=1000, walk_levels=4, value_blocks=1
    )
    assert cost.quantity == 100 * 1000
    assert cost.device_ms == pytest.approx(100 * 1000 * 1e3 / 1e6)
    assert cost.unit == "hh_lanes"


# ---------------------------------------------------------------------------
# TokenBucket
# ---------------------------------------------------------------------------


def test_token_bucket_burst_refill_and_hint():
    clock = FakeClock()
    bucket = TokenBucket(rate=10.0, burst=5.0, clock=clock)
    assert bucket.try_take(5)
    assert not bucket.try_take(1)
    assert bucket.time_until(1) == pytest.approx(0.1)
    clock.advance(0.25)  # refills 2.5 tokens
    assert bucket.try_take(2)
    assert bucket.tokens == pytest.approx(0.5)
    clock.advance(100.0)  # refill clamps at burst
    assert bucket.tokens == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# WeightedFairQueue
# ---------------------------------------------------------------------------


def test_wfq_single_tenant_is_exact_fifo():
    q = WeightedFairQueue()
    for i in range(50):
        q.push(i, tenant="only", cost=float(1 + i % 3))
    assert q.drain() == list(range(50))


def test_wfq_backlogged_shares_follow_weights():
    q = WeightedFairQueue()
    weights = {"a": 3.0, "b": 2.0, "c": 1.0}
    for i in range(120):
        for tenant, w in weights.items():
            q.push((tenant, i), tenant=tenant, weight=w)
    first = [q.pop()[0] for _ in range(60)]
    total_w = sum(weights.values())
    for tenant, w in weights.items():
        share = first.count(tenant) / len(first)
        assert share == pytest.approx(w / total_w, rel=0.15)


def test_wfq_idle_tenant_cannot_burst_ahead_of_backlog():
    q = WeightedFairQueue()
    for i in range(10):
        q.push(("busy", i), tenant="busy")
    for _ in range(5):
        q.pop()
    # A newly-arriving tenant starts at the advanced virtual time: it
    # interleaves with the remaining backlog instead of jumping all of
    # it (start tags equal => arrival order breaks the tie).
    q.push(("late", 0), tenant="late")
    drained = q.drain()
    assert drained[0] == ("busy", 5)
    assert ("late", 0) in drained[:3]


# ---------------------------------------------------------------------------
# AdmissionController: every shed reason, exactly once each
# ---------------------------------------------------------------------------


def test_admission_quota_shed_with_refill_hint(tmp_path):
    clock = FakeClock()
    adm = AdmissionController(
        pinned_model(tmp_path), queue_budget_ms=10_000.0, clock=clock
    )
    adm.set_tenant("t", TenantPolicy(rate_qps=10.0, burst=4.0))
    assert adm.admit(4, tenant="t").admitted
    decision = adm.admit(2, tenant="t")
    assert not decision.admitted
    assert decision.reason is ShedReason.QUOTA
    assert decision.retry_after_s == pytest.approx(0.2)
    clock.advance(0.2)
    assert adm.admit(2, tenant="t").admitted


def test_admission_sheds_doomed_request_before_queue_budget(tmp_path):
    clock = FakeClock(100.0)
    adm = AdmissionController(
        pinned_model(tmp_path), queue_budget_ms=1000.0, clock=clock
    )
    assert adm.admit(500).admitted  # 500 ms outstanding
    # 100 more keys => 600 ms drain, but only 200 ms until deadline:
    # doomed, shed with a drain-the-gap hint — even though the queue
    # budget (1000 ms) has room.
    decision = adm.admit(100, deadline=clock.t + 0.2)
    assert not decision.admitted
    assert decision.reason is ShedReason.DRAIN_DEADLINE
    assert decision.retry_after_s == pytest.approx(0.4)


def test_admission_queue_cost_budget_and_release(tmp_path):
    adm = AdmissionController(
        pinned_model(tmp_path), queue_budget_ms=100.0,
        clock=FakeClock(),
        metrics=MetricsRegistry(),
    )
    first = adm.admit(80)
    assert first.admitted and adm.outstanding_ms == pytest.approx(80.0)
    decision = adm.admit(40)
    assert not decision.admitted
    assert decision.reason is ShedReason.QUEUE_COST
    assert decision.retry_after_s > 0
    adm.release(first.cost)
    assert adm.outstanding_ms == 0.0
    assert adm.admit(40).admitted
    counters = adm.metrics.export()["counters"]
    assert counters["admission.shed{reason=queue_cost}"] == 1
    assert counters["admission.admitted"] == 2


def test_admission_priority_floor_sheds_best_effort(tmp_path):
    adm = AdmissionController(
        pinned_model(tmp_path), queue_budget_ms=1000.0, clock=FakeClock()
    )
    adm.set_tenant("batch", TenantPolicy(priority=0))
    adm.set_tenant("vip", TenantPolicy(priority=2))
    adm.set_min_priority(1)
    shed = adm.admit(1, tenant="batch")
    assert not shed.admitted and shed.reason is ShedReason.PRIORITY
    assert adm.admit(1, tenant="vip").admitted
    assert adm.admit(1, tenant="unregistered").admitted  # default prio 1
    adm.set_min_priority(2)
    assert not adm.admit(1, tenant="unregistered").admitted
    adm.set_min_priority(0)
    assert adm.admit(1, tenant="batch").admitted
    export = adm.export()
    assert export["tenants"]["batch"]["shed"] == 1
    assert export["tenants"]["vip"]["admitted"] == 1


# ---------------------------------------------------------------------------
# BrownoutController: hysteretic engage/escalate/revert
# ---------------------------------------------------------------------------


def test_brownout_full_ladder_and_full_revert():
    clock = FakeClock()
    breaching = [True]
    engaged, reverted = [], []
    bc = BrownoutController(
        signal=lambda: breaching[0],
        engage_after_s=0.0,
        escalate_after_s=5.0,
        revert_after_s=10.0,
        metrics=MetricsRegistry(),
        clock=clock,
    )
    for step in BROWNOUT_STEPS:
        bc.add_step_action(
            step,
            lambda s=step: engaged.append(s),
            lambda s=step: reverted.append(s),
        )
    assert bc.evaluate() == 1  # engages on first breach observation
    assert bc.evaluate() == 1  # escalation hysteresis holds
    for want in (2, 3, 4):
        clock.advance(5.0)
        assert bc.evaluate() == want
    clock.advance(5.0)
    assert bc.evaluate() == 4  # ladder is exhausted, stays put
    assert engaged == list(BROWNOUT_STEPS)
    assert bc.active_steps() == BROWNOUT_STEPS

    breaching[0] = False
    assert bc.evaluate() == 4  # healthy, but not for long enough yet
    for want in (3, 2, 1, 0):
        clock.advance(10.0)
        assert bc.evaluate() == want
    clock.advance(10.0)
    assert bc.evaluate() == 0
    assert reverted == list(reversed(BROWNOUT_STEPS))
    counters = bc.metrics.export()["counters"]
    assert counters["brownout.engaged{step=critical_only}"] == 1
    assert counters["brownout.reverted{step=shed_low_priority}"] == 1
    export = bc.export()
    assert export["level"] == 0
    assert len(export["transitions"]) == 8
    assert [t["action"] for t in export["transitions"][:4]] == ["engage"] * 4


def test_brownout_breach_resets_revert_clock():
    clock = FakeClock()
    breaching = [True]
    bc = BrownoutController(
        signal=lambda: breaching[0],
        escalate_after_s=60.0,
        revert_after_s=10.0,
        clock=clock,
    )
    assert bc.evaluate() == 1
    breaching[0] = False
    clock.advance(9.0)
    assert bc.evaluate() == 1  # almost healthy long enough...
    breaching[0] = True
    assert bc.evaluate() == 1  # ...but the breach resets the clock
    breaching[0] = False
    clock.advance(9.0)
    assert bc.evaluate() == 1  # only 0 s healthy again at this point
    clock.advance(9.0)
    assert bc.evaluate() == 1  # 9 s — without the reset this reverts
    clock.advance(1.5)
    assert bc.evaluate() == 0


def test_brownout_force_level_runs_crossed_actions():
    log = []
    bc = BrownoutController(signal=lambda: False, clock=FakeClock())
    for step in BROWNOUT_STEPS:
        bc.add_step_action(
            step,
            lambda s=step: log.append(("engage", s)),
            lambda s=step: log.append(("revert", s)),
        )
    bc.force_level(3)
    assert log == [("engage", s) for s in BROWNOUT_STEPS[:3]]
    log.clear()
    bc.force_level(0)
    assert log == [("revert", s) for s in reversed(BROWNOUT_STEPS[:3])]


def test_brownout_action_error_does_not_stall_ladder():
    def boom():
        raise RuntimeError("step exploded")

    bc = BrownoutController(
        signal=lambda: True,
        clock=FakeClock(),
        metrics=MetricsRegistry(),
    )
    bc.add_step_action("shed_low_priority", boom, boom)
    assert bc.evaluate() == 1
    assert bc.metrics.export()["counters"]["brownout.action_errors"] == 1
    assert bc.export()["transitions"][0]["action_error"].startswith(
        "RuntimeError"
    )


def test_brownout_rejects_unknown_step():
    bc = BrownoutController(signal=lambda: False)
    with pytest.raises(ValueError):
        bc.add_step_action("power_cycle", lambda: None, lambda: None)


def test_brownout_slo_tracker_duck_typing():
    class FakeSlo:
        def __init__(self):
            self.breaching = True

        def breaches(self, evaluate=False):
            return [{"name": "x"}] if self.breaching else []

    slo = FakeSlo()
    bc = BrownoutController(slo=slo, clock=FakeClock())
    assert bc.evaluate() == 1
    slo.breaching = False
    clock_steps = bc  # revert_after defaults to 10 s of the fake clock
    # (no advance: the fake clock never moves, so no revert yet)
    assert clock_steps.evaluate() == 1
