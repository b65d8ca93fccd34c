"""trace_reduce.py on traces with known answers: a hand-written XSpace
(busy union, op durations and modules, idle gaps and their host labels)
and a small trace recorded on a v5e chip (`record_trace.py`)."""

import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "synthetic.xplane.pbtxt")) as f:
        return trace_reduce.reduce(ProfileData.from_text_proto(f.read()))


def test_window_is_the_annotation(synthetic):
    assert synthetic.window_ns == 100000
    assert synthetic.devices == 1


def test_busy_is_the_union_clipped_to_the_window(synthetic):
    assert synthetic.busy_ns == 22000
    assert synthetic.idle_share == pytest.approx(0.78)


def test_op_durations_and_modules(synthetic):
    assert synthetic.ops["fusion.1"].ns == 6000
    assert synthetic.ops["fusion.1"].count == 2
    assert synthetic.ops["copy.2"].ns == 5000  # clipped at the window
    assert synthetic.ops["_ip_kernel_v2"].module == "jit__ip_pallas_staged_v2(2)"
    assert synthetic.op_ns("ip_pallas_staged", field="module") == 11000
    assert synthetic.op_ns(r"_ip_kernel") == 11000
    assert synthetic.top_ops(1) == [("_ip_kernel_v2", 11000 / 1e9)]


def test_idle_gaps_carry_the_overlapping_host_annotation(synthetic):
    assert synthetic.top_gaps(3) == [
        ("leader.request_ms", 45000 / 1e9),
        ("helper_decrypt", 33000 / 1e9),
    ]


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (2, 3), (1, 4)]) == [
        (0, 4), (5, 7)
    ]


RECORDED = os.path.join(DATA, "recorded_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    reduction = trace_reduce.reduce_file(RECORDED)
    assert reduction.devices == 1
    assert 0 < reduction.busy_ns < reduction.window_ns
    label, seconds = reduction.top_gaps(1)[0]
    assert label == "host.sleep"
    assert 0.04 < seconds < 0.06
    # The Pallas kernel is a `tpu_custom_call` op inside its program.
    kernel = reduction.op_ns(r'custom_call_target="tpu_custom_call"')
    assert kernel > 0
    assert reduction.op_ns("pallas_step", field="module") == kernel
