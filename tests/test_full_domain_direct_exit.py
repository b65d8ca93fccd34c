"""The full-domain expansion's direct exit and lane-dense leaf stage
(`dpf._direct_exit`, `dpf._leaf_stage_lanes`): bit-identical to the limb
program and to the gather exit, with no per-leaf gather and nothing
dispatched after the jitted leaf stage. Small CPU cases, Pallas kernels
in interpret mode."""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_point_functions_tpu import dpf as dpf_mod
from distributed_point_functions_tpu.dpf import (
    DistributedPointFunction,
    DpfParameters,
)
from distributed_point_functions_tpu.observability import tracing
from distributed_point_functions_tpu.ops import aes
from distributed_point_functions_tpu.ops import expand_planes_pallas as epp
from distributed_point_functions_tpu.value_types import IntType, TupleType

RNG = np.random.default_rng(25)


@pytest.fixture(scope="module")
def interpret_kernels():
    """The planes program with the TPU's per-level kernels, interpreted;
    its compiled programs are shared by the module's cases (one tree
    depth) and dropped afterwards."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DPF_TPU_LEVEL_KERNEL", "pallas")
        for name in ("expand_level_planes_pallas",
                     "value_hash_planes_pallas"):
            mp.setattr(epp, name,
                       functools.partial(getattr(epp, name), interpret=True))
        dpf_mod._expand_levels_planes_fn.cache_clear()
        try:
            yield
        finally:
            dpf_mod._expand_levels_planes_fn.cache_clear()


def _evaluate(d, keys, prefixes):
    """Both parties: the full domain, then (incremental) the next level
    under `prefixes`; returns the outputs and the `dpf.expand` exits."""
    outs = []
    tracing.span_log.start()
    try:
        for k in keys:
            ctx = d.create_evaluation_context(k)
            outs.append(jax.tree_util.tree_map(
                np.asarray, d.evaluate_next([], ctx)))
            if prefixes:
                outs.append(jax.tree_util.tree_map(
                    np.asarray, d.evaluate_next(prefixes, ctx)))
    finally:
        records = tracing.span_log.drain()["records"]
    exits = {
        (r["attrs"] or {}).get("exit")
        for r in records if r["name"] == "dpf.expand"
    }
    return outs, exits


@pytest.mark.parametrize("params,alpha,betas,prefixes,exit", [
    # One tree depth, six levels from one seed (five in limb space, one
    # plane level), for every case: the cases share their programs.
    ([DpfParameters(8, IntType(32))], 200, [0xFFFFFFF1], None, "direct"),
    ([DpfParameters(7, IntType(64))], 93, [(1 << 64) - 5], None, "direct"),
    ([DpfParameters(6, IntType(128))], 41, [(1 << 127) + 9], None, "direct"),
    # Incremental: one level, then the same six levels under one
    # prefix, whose span the hierarchical path selects.
    ([DpfParameters(2, IntType(64)), DpfParameters(8, IntType(64))],
     200, [3, (1 << 63) + 1], [3], "direct"),
    # A tuple keeps the gather exit and the pytree leaf stage.
    ([DpfParameters(2, TupleType((IntType(32), IntType(32))))],
     3, [(7, 0xFFFFFFFF)], None, "gather"),
], ids=["u32-ld8", "u64-ld7", "u128-ld6", "u64-incremental-ld2-8",
        "tuple-ld2"])
def test_full_domain_planes_matches_limb(monkeypatch, interpret_kernels,
                                         params, alpha, betas, prefixes,
                                         exit):
    d = DistributedPointFunction.create_incremental(params)
    keys = (d.generate_keys_incremental(alpha, betas) if len(params) > 1
            else d.generate_keys(alpha, betas[0]))
    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "limb")
    want, want_exits = _evaluate(d, keys, prefixes)
    assert want_exits == {"gather"}

    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    returned = []
    stage = dpf_mod._leaf_stage_lanes

    def leaf_stage(*args, **kwargs):
        returned.append(stage(*args, **kwargs))
        return returned[-1]

    monkeypatch.setattr(dpf_mod, "_leaf_stage_lanes", leaf_stage)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no silent XLA fallback
        got, got_exits = _evaluate(d, keys, prefixes)
    assert got_exits == {exit}
    for w, g in zip(want, got):
        jax.tree_util.tree_map(np.testing.assert_array_equal, g, w)
    if exit == "direct":
        # Every evaluation returned the leaf stage's own array: no
        # reshape or copy was dispatched after it.
        assert len(returned) == len(got)


def _old_leaf_values(state, ctrl, order, n0, vt, vc, cepb, party):
    seeds, control = dpf_mod._gather_exit(state, ctrl, order, n0)
    vc_dev = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[vt.dev_const(int(v), ()) for v in vc])
    values = dpf_mod._leaf_stage(seeds, control, vc_dev, vtype=vt,
                                 cepb=cepb, num_blocks=1, party=party,
                                 pre_hashed=True)
    return values.reshape((-1,) + values.shape[2:])


@pytest.mark.parametrize("bits,cepb,plane_levels,n0,party", [
    (64, 2, 8, 32, 1),   # two row gathers (2 x 128 positions)
    (8, 4, 3, 32, 0),    # sub-limb elements, fewer than fit a block
    (128, 1, 0, 8, 1),   # one position, prefixes padded to the word
])
def test_direct_exit_matches_gather_exit(bits, cepb, plane_levels, n0,
                                         party):
    """From random leaf planes: direct exit + lane-dense leaf stage equal
    gather exit + `_leaf_stage` + flatten, for every integer width."""
    vt = IntType(bits)
    w = 1 << plane_levels
    state = jnp.asarray(RNG.integers(0, 1 << 32, (16, 8, w), np.uint32))
    ctrl = jnp.asarray(RNG.integers(0, 1 << 32, (w,), np.uint32))
    order = dpf_mod._bitrev_order(plane_levels)
    vc = [int(v) for v in RNG.integers(0, 1 << 63, cepb, np.uint64)]
    vc = [(v << 65 | v) % (1 << bits) for v in vc]
    # Each side jitted whole: one compile, not one per eager op.
    want = jax.jit(functools.partial(
        _old_leaf_values, order=order, n0=n0, vt=vt, vc=vc, cepb=cepb,
        party=party,
    ))(state, ctrl)

    @jax.jit
    def direct(state, ctrl):
        limbs, c = dpf_mod._direct_exit(state, ctrl, order, n0)
        return dpf_mod._leaf_stage_lanes(
            limbs, c, np.stack([vt.host_const(v) for v in vc]), bits=bits,
            cepb=cepb, party=party,
        )

    got = direct(state, ctrl)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _gathers(jaxpr):
    """Every gather in a jaxpr and the jaxprs nested in its equations."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _gathers(inner)


def test_full_domain_program_has_no_leaf_gather(monkeypatch):
    """From the staged key to the returned value, the TPU's full-domain
    program (per-level kernels, direct exit, lane-dense leaf stage) moves
    no single leaf: a gather over as many elements as there are leaves
    moves a row of >= 32 leaves an index. The gather exit, for contrast,
    gathers every leaf."""
    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "pallas")
    d = DistributedPointFunction.create(DpfParameters(10, IntType(64)))
    key, _ = d.generate_keys(777, 5)
    stop = d._hierarchy_to_tree[0]
    leaves = 1 << stop
    args = (
        jnp.asarray(aes.u128_to_limbs(key.seed))[None, :],
        jnp.asarray(np.array([key.party], dtype=np.uint32)),
        *d._device_correction_words(key, 0, stop),
    )
    assert dpf_mod._leaf_exit(1, stop) == "direct"

    def program(exit):
        run = dpf_mod._expand_levels_fn(stop, hash_leaves=True, exit=exit)

        def full_domain(*a):
            if exit == "gather":
                return run(*a)
            limbs, ctrl = run(*a)
            return dpf_mod._leaf_stage_lanes(
                limbs, ctrl, np.zeros((2, 2), np.uint32), bits=64, cepb=2,
                party=1,
            )

        closed = jax.make_jaxpr(full_domain)(*args)
        return [int(np.prod(g.invars[1].aval.shape[:-1]))
                for g in _gathers(closed.jaxpr)
                if g.invars[0].aval.size >= leaves]

    try:
        direct = program("direct")
        gather = program("gather")
    finally:
        dpf_mod._expand_levels_planes_fn.cache_clear()
    assert direct and max(direct) <= leaves // 32, direct
    assert max(gather) == leaves, gather
