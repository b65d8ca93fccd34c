"""Hierarchical evaluation over many prefixes (`evaluate_until` with
prefix arrays, the frontier kept on the device, the rows exit) against
the plain reference, at the upstream experiment's shape scaled down:
domain 2^12, levels {5, 7, 9, 11, 12}, uint32 values, the prefixes of
128 uniform nonzeros."""

import jax
import numpy as np
import pytest

from distributed_point_functions_tpu import dpf as dpf_mod
from distributed_point_functions_tpu import serialization as ser
from distributed_point_functions_tpu.dpf import (
    DistributedPointFunction,
    DpfParameters,
)
from distributed_point_functions_tpu.observability import tracing
from distributed_point_functions_tpu.protos import (
    distributed_point_function_pb2 as dpf_pb2,
)
from distributed_point_functions_tpu.testing.plain_dpf import PlainDpf
from distributed_point_functions_tpu.value_types import IntType

LDS = [5, 7, 9, 11, 12]
RNG = np.random.default_rng(26)
NONZEROS = np.sort(RNG.choice(1 << LDS[-1], 128, replace=False)).astype(
    np.uint64
)
ALPHA = int(NONZEROS[41])
BETAS = [int(b) for b in RNG.integers(1, 1 << 32, len(LDS))]
PREFIXES = [None] + [
    np.unique(NONZEROS >> np.uint64(LDS[-1] - lds)) for lds in LDS[:-1]
]


@pytest.fixture(scope="module")
def dpf():
    return DistributedPointFunction.create_incremental(
        [DpfParameters(lds, IntType(32)) for lds in LDS]
    )


@pytest.fixture(scope="module")
def keys(dpf):
    return dpf.generate_keys_incremental(ALPHA, BETAS)


def _hierarchy(dpf, key, as_list=False, start=0, ctx=None):
    ctx = ctx or dpf.create_evaluation_context(key)
    outs = []
    for level in range(start, len(LDS)):
        prefixes = [] if level == 0 else PREFIXES[level]
        if as_list and level:
            prefixes = [int(p) for p in prefixes]
        outs.append(np.asarray(dpf.evaluate_until(level, prefixes, ctx)))
    return outs, ctx


def _indices(level):
    if level == 0:
        return np.arange(1 << LDS[0], dtype=np.uint64)
    opp = 1 << (LDS[level] - LDS[level - 1])
    return (PREFIXES[level][:, None] * np.uint64(opp)
            + np.arange(opp, dtype=np.uint64)).reshape(-1)


@pytest.fixture(scope="module")
def outputs(dpf, keys):
    return [_hierarchy(dpf, k)[0] for k in keys]


def test_every_output_matches_the_plain_reference(keys, outputs):
    ref = PlainDpf(LDS, 32)
    for level, lds in enumerate(LDS):
        index = _indices(level)
        shares = [o[level][:, 0].astype(np.uint64) for o in outputs]
        assert [len(s) for s in shares] == [len(index)] * 2
        for key, share in zip(keys, shares):
            np.testing.assert_array_equal(
                share, ref.outputs(key, level, index)
            )
        total = (shares[0] + shares[1]) & np.uint64(0xFFFFFFFF)
        hit = index == np.uint64(ALPHA >> (LDS[-1] - lds))
        assert hit.sum() == 1
        np.testing.assert_array_equal(
            total, np.where(hit, np.uint64(BETAS[level]), np.uint64(0))
        )


def test_list_prefixes_equal_array_prefixes(dpf, keys, outputs):
    got, _ = _hierarchy(dpf, keys[1], as_list=True)
    for g, want in zip(got, outputs[1]):
        np.testing.assert_array_equal(g, want)


def test_unsorted_prefixes_give_one_row_per_prefix_in_order(dpf, keys,
                                                            outputs):
    """Any order, repeats included, and not every leaf of the level before
    (so the frontier is gathered by position): the output rows follow
    the caller."""
    ctx = dpf.create_evaluation_context(keys[0])
    for level in range(4):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], ctx)
    dropped = np.isin(PREFIXES[4] >> np.uint64(2), PREFIXES[3][::9])
    keep = np.flatnonzero(~dropped)
    order = RNG.permutation(keep)
    order = np.concatenate([order, order[:5]])
    got = np.asarray(dpf.evaluate_until(4, PREFIXES[4][order], ctx))
    want = outputs[0][4].reshape(len(PREFIXES[4]), -1)
    np.testing.assert_array_equal(got.reshape(len(order), -1), want[order])


def test_serialized_context_resumes_bit_identically(dpf, keys, outputs):
    ctx = dpf.create_evaluation_context(keys[0])
    for level in range(3):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], ctx)
    assert isinstance(ctx.partial_evaluations,
                      dpf_mod.DevicePartialEvaluations)
    before = tracing.runtime_counters.get("dpf.hier.readback_bytes")
    proto = ser.evaluation_context_to_proto(dpf, ctx)
    # Serializing is the one read of the frontier: 16 bytes a node.
    assert tracing.runtime_counters.get("dpf.hier.readback_bytes") == (
        before + 16 * len(np.unique(PREFIXES[2] >> np.uint64(2)))
    )
    dpf2, ctx2 = ser.evaluation_context_from_proto(
        dpf_pb2.EvaluationContext.FromString(proto.SerializeToString())
    )
    resumed, _ = _hierarchy(dpf2, None, start=3, ctx=ctx2)
    continued, _ = _hierarchy(dpf, None, start=3, ctx=ctx)
    for level, (r, c) in enumerate(zip(resumed, continued), start=3):
        np.testing.assert_array_equal(r, outputs[0][level])
        np.testing.assert_array_equal(c, outputs[0][level])


def test_unsorted_level_keeps_its_leaves_for_the_next(dpf, keys, outputs):
    """A level evaluated under prefixes in any order, with repeats, keeps
    the leaves its outputs were read from keyed by tree index, and the
    next level resumes from them by position."""
    ctx = dpf.create_evaluation_context(keys[1])
    for level in range(3):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], ctx)
    order = RNG.permutation(len(PREFIXES[3]))
    order = np.concatenate([order, order[:7]])
    got = np.asarray(dpf.evaluate_until(3, PREFIXES[3][order], ctx))
    want = outputs[1][3].reshape(len(PREFIXES[3]), -1)
    np.testing.assert_array_equal(got.reshape(len(order), -1), want[order])
    assert ctx._leaves.row_of is not None
    before = tracing.runtime_counters.get("dpf.hier.readback_bytes")
    np.testing.assert_array_equal(
        np.asarray(dpf.evaluate_until(4, PREFIXES[4], ctx)), outputs[1][4]
    )
    assert tracing.runtime_counters.get("dpf.hier.readback_bytes") == before


def test_nodes_outside_the_kept_leaves_walk_from_partial_evaluations(
        dpf, keys):
    """A prefix whose leaf the level before did not read, though its
    parent node was expanded there, resumes by walking from the
    partial evaluations on the device: every output still equals the
    plain reference's."""
    ctx = dpf.create_evaluation_context(keys[0])
    for level in range(3):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], ctx)
    # Drop one level-3 prefix whose node (p >> 2) keeps another prefix.
    node = PREFIXES[3] >> np.uint64(2)
    shared = np.flatnonzero(node[1:] == node[:-1])
    assert len(shared)
    dropped = PREFIXES[3][shared[0]]
    dpf.evaluate_until(3, np.delete(PREFIXES[3], shared[0]), ctx)
    before = tracing.runtime_counters.get("dpf.hier.readback_bytes")
    prefixes = np.concatenate([PREFIXES[4][:20],
                               [dropped * np.uint64(4) + np.uint64(1)]])
    got = np.asarray(dpf.evaluate_until(4, prefixes, ctx))[:, 0]
    index = (prefixes[:, None] * np.uint64(2)
             + np.arange(2, dtype=np.uint64)).reshape(-1)
    np.testing.assert_array_equal(
        got.astype(np.uint64), PlainDpf(LDS, 32).outputs(keys[0], 4, index)
    )
    assert tracing.runtime_counters.get("dpf.hier.readback_bytes") == before


def test_evaluate_at_with_a_context_resumes_from_its_frontier(dpf, keys,
                                                             outputs):
    """`evaluate_at` with a context walks from the context's frontier on
    the device (no read-back), equals `evaluate_at` without one, and
    leaves the points' nodes for `evaluate_until` to resume from."""
    key = keys[1]
    ctx = dpf.create_evaluation_context(key)
    for level in range(3):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], ctx)
    points = PREFIXES[4][::3]
    before = tracing.runtime_counters.get("dpf.hier.readback_bytes")
    got = np.asarray(dpf.evaluate_at(key, 3, [int(p) for p in points], ctx))
    np.testing.assert_array_equal(
        got, np.asarray(dpf.evaluate_at(key, 3, points))
    )
    np.testing.assert_array_equal(
        got[:, 0].astype(np.uint64), PlainDpf(LDS, 32).outputs(key, 3, points)
    )
    assert tracing.runtime_counters.get("dpf.hier.readback_bytes") == before
    assert isinstance(ctx.partial_evaluations,
                      dpf_mod.DevicePartialEvaluations)
    assert (ctx.previous_hierarchy_level, ctx.partial_evaluations_level) == (
        3, 3)
    want = outputs[1][4].reshape(len(PREFIXES[4]), -1)
    np.testing.assert_array_equal(
        np.asarray(dpf.evaluate_until(4, points, ctx)).reshape(len(points), -1),
        want[::3],
    )
    with pytest.raises(ValueError,
                       match=r"^evaluation point 4096 out of range$"):
        dpf.evaluate_at(key, 4, [5, 4096])


def test_frontiers_past_the_device_budget_move_to_the_host(dpf, keys,
                                                           outputs,
                                                           monkeypatch):
    """Past `FRONTIER_DEVICE_BYTES` the context evaluated least recently
    moves its partial evaluations to the host (counted as read-back) and
    drops its leaves; it then resumes by walking, bit-identically."""
    monkeypatch.setattr(dpf_mod, "FRONTIER_DEVICE_BYTES", 1)
    old = dpf.create_evaluation_context(keys[0])
    new = dpf.create_evaluation_context(keys[0])
    for level in range(3):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], old)
    assert isinstance(old.partial_evaluations,
                      dpf_mod.DevicePartialEvaluations)
    before = tracing.runtime_counters.get("dpf.hier.readback_bytes")
    dpf.evaluate_until(0, [], new)
    assert isinstance(old.partial_evaluations, dpf_mod.PartialEvaluations)
    assert old._leaves is None and new._leaves is not None
    assert tracing.runtime_counters.get("dpf.hier.readback_bytes") == (
        before + 16 * len(old.partial_evaluations)
    )
    resumed, _ = _hierarchy(dpf, None, start=3, ctx=old)
    for level, r in enumerate(resumed, start=3):
        np.testing.assert_array_equal(r, outputs[0][level])


def test_prefix_errors_are_unchanged(dpf, keys):
    ctx = dpf.create_evaluation_context(keys[0])
    dpf.evaluate_until(0, [], ctx)
    with pytest.raises(ValueError, match=r"^prefix 32 out of range$"):
        dpf.evaluate_until(1, np.array([3, 32, 40], dtype=np.uint64), ctx)
    with pytest.raises(ValueError, match=r"^prefix -1 out of range$"):
        dpf.evaluate_until(1, [3, -1], ctx)
    for level in (1, 2, 3):
        dpf.evaluate_until(level, PREFIXES[level], ctx)
    # Level 4's prefixes (11 bits) lie in tree nodes p >> 2 whose parents
    # at level 3's starting tree level (p >> 4, 7 bits) must be among the
    # nodes expanded there; name the first absent one, in caller order.
    expanded = set((PREFIXES[3] >> np.uint64(2)).tolist())
    absent = [v for v in range(128) if v not in expanded][:2]
    prefixes = [int(PREFIXES[4][0]), absent[1] << 4, absent[0] << 4]
    with pytest.raises(
        ValueError,
        match=rf"^prefix {absent[1]} not present in "
              r"ctx.partial_evaluations at hierarchy level 3$",
    ):
        dpf.evaluate_until(4, prefixes, ctx)


def test_spans_and_counters(dpf, keys):
    before = tracing.runtime_counters.export()
    tracing.span_log.start()
    try:
        _hierarchy(dpf, keys[0])
    finally:
        records = tracing.span_log.drain()["records"]
    names = [r["name"] for r in records]
    assert names.count("dpf.prefixes") == len(LDS) - 1
    assert [r["attrs"]["level"] for r in records
            if r["name"] == "dpf.evaluate_until"] == list(range(len(LDS)))
    for r in records:
        if r["name"] == "dpf.prefixes":
            assert r["parent"] == "dpf.evaluate_until"
    after = tracing.runtime_counters.export()
    delta = {name: after.get(name, 0) - before.get(name, 0)
             for name in ("dpf.hier.evaluations", "dpf.hier.readback_bytes",
                          "dpf.exit.gather_leaves")}
    assert delta == {"dpf.hier.evaluations": len(LDS) - 1,
                     "dpf.hier.readback_bytes": 0,
                     "dpf.exit.gather_leaves": 0}


@pytest.mark.parametrize("nodes,levels", [(48, 2), (100, 1), (256, 2)])
def test_rows_exit_matches_gather_exit(nodes, levels):
    """The many-node exit (`_chunk_limb_rows`, read at `_Leaves`
    positions) holds the leaves `_gather_exit` puts in natural order,
    on random planes: [positions x nodes] lanes, pure doubling."""
    n_pad = dpf_mod._next_pow2(nodes)
    g = (n_pad << levels) // 32
    t = min(1024, n_pad // 32)
    rng = np.random.default_rng(nodes)
    state = rng.integers(0, 1 << 32, (16, 8, g), dtype=np.uint32)
    ctrl = rng.integers(0, 1 << 32, g, dtype=np.uint32)
    order = dpf_mod._bitrev_order(levels)
    limbs, control = jax.jit(
        lambda s, c: dpf_mod._gather_exit(s, c, order, n_pad)
    )(state, ctrl)
    chunks = state.reshape(16, 8, g // t, t).transpose(2, 0, 1, 3)
    rows = jax.jit(dpf_mod._chunk_limb_rows)(chunks)
    leaves = dpf_mod._Leaves(rows=rows, levels=levels, layout="planes",
                             n32=n_pad)
    q, c = np.divmod(np.arange(nodes << levels), 1 << levels)
    idx = leaves.position(q, c).astype(np.int32)
    got = np.asarray(dpf_mod._gather_rows(rows, idx))
    np.testing.assert_array_equal(got, np.asarray(limbs)[: nodes << levels])
    words = ctrl[idx >> 5] >> (idx & 31).astype(np.uint32)
    np.testing.assert_array_equal(
        words & 1, np.asarray(control)[: nodes << levels]
    )


def test_plane_program_matches_limb_program(dpf, keys, outputs,
                                            monkeypatch):
    """The plane program's rows exit (XLA levels here; the chip runs its
    Pallas kernels) gives the limb program's outputs, resuming from the
    limb program's leaves and then from its own."""
    ctx = dpf.create_evaluation_context(keys[0])
    for level in range(3):
        dpf.evaluate_until(level, [] if level == 0 else PREFIXES[level], ctx)
    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    tracing.span_log.start()
    try:
        got, _ = _hierarchy(dpf, None, start=3, ctx=ctx)
    finally:
        records = tracing.span_log.drain()["records"]
    for level, g in enumerate(got, start=3):
        np.testing.assert_array_equal(g, outputs[0][level])
    exits = [r["attrs"]["exit"] for r in records if r["name"] == "dpf.expand"]
    assert exits == ["rows", "rows"]
