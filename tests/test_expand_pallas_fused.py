"""Differential tests for the fused multi-level Pallas expansion kernels
(the tail and walk-descent families, interpret mode on CPU) against
their XLA twins — split from `test_expand_pallas.py` (the per-level,
value-hash and path kernels) so the two halves run on separate test
workers."""


import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_point_functions_tpu import keys as fixed_keys
from distributed_point_functions_tpu.ops.aes_bitslice import (
    mmo_hash_planes,
)
from distributed_point_functions_tpu.ops.expand_planes_pallas import (
    expand_level_planes_pallas,
    value_hash_planes_pallas,
)
from distributed_point_functions_tpu.pir.dense_eval_planes import (
    _tile_keys,
    expand_level_planes,
    pack_key_bits,
    pack_key_planes,
)

RNG = np.random.default_rng(23)


def _random_inputs(g, nk):
    kg = nk // 32
    assert g % kg == 0
    state = RNG.integers(0, 1 << 32, (16, 8, g), dtype=np.uint32)
    ctrl = RNG.integers(0, 1 << 32, (g,), dtype=np.uint32)
    cw = RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    cwl = RNG.integers(0, 2, (nk,), dtype=np.uint32)
    cwr = RNG.integers(0, 2, (nk,), dtype=np.uint32)
    return state, ctrl, cw, cwl, cwr


@pytest.mark.parametrize(
    "g0,nk,r,tile",
    [(12, 96, 2, 6), (2, 64, 3, 2)],
)
def test_tail_kernel_matches_xla(g0, nk, r, tile):
    """The fused multi-level tail kernel (interpret mode) is
    bit-identical to per-tile XLA levels + value hash, in tiled order.
    (The minimal r=1 multi-tile case lives in the fast tier,
    `test_pallas_fast.py`.)"""
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        expand_tail_planes_pallas,
    )

    kg = nk // 32
    state = jnp.asarray(
        RNG.integers(0, 1 << 32, (16, 8, g0), dtype=np.uint32)
    )
    ctrl = jnp.asarray(RNG.integers(0, 1 << 32, (g0,), dtype=np.uint32))
    cwp_kg = [
        pack_key_planes(
            jnp.asarray(RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32))
        )
        for _ in range(r)
    ]
    cwl_kg = [
        pack_key_bits(
            jnp.asarray(RNG.integers(0, 2, (nk,), dtype=np.uint32))
        )
        for _ in range(r)
    ]
    cwr_kg = [
        pack_key_bits(
            jnp.asarray(RNG.integers(0, 2, (nk,), dtype=np.uint32))
        )
        for _ in range(r)
    ]
    vc_kg = pack_key_planes(
        jnp.asarray(RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32))
    )

    # XLA twin: per tile, r global-order levels then the value hash —
    # jitted per tile shape (one compile, reused across tiles; the eager
    # bitsliced-AES dispatch is what made this module cost minutes).
    @jax.jit
    def twin_tile(s, c, cwp_all, cwl_all, cwr_all, vc):
        for i in range(r):
            g2 = 2 * s.shape[-1]
            s, c = expand_level_planes(
                s,
                c,
                _tile_keys(cwp_all[i], g2),
                _tile_keys(cwl_all[i], g2 // 2),
                _tile_keys(cwr_all[i], g2 // 2),
            )
        v = mmo_hash_planes(fixed_keys.RK_VALUE, s) ^ (
            _tile_keys(vc, s.shape[-1]) & c[None, None, :]
        )
        return v, c

    cwp_st = jnp.stack(cwp_kg)
    cwl_st = jnp.stack(cwl_kg)
    cwr_st = jnp.stack(cwr_kg)
    outs = []
    out_ctrls = []
    for lo in range(0, g0, tile):
        v, c = twin_tile(
            state[:, :, lo : lo + tile], ctrl[lo : lo + tile],
            cwp_st, cwl_st, cwr_st, vc_kg,
        )
        outs.append(v)
        out_ctrls.append(c)
    want = np.asarray(jnp.concatenate(outs, axis=-1))
    want_ctrl = np.asarray(jnp.concatenate(out_ctrls))

    got_v, got_c = expand_tail_planes_pallas(
        state,
        ctrl,
        jnp.stack(cwp_kg),
        jnp.stack(cwl_kg),
        jnp.stack(cwr_kg),
        vc_kg,
        tile_lanes=tile,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_v), want)
    np.testing.assert_array_equal(np.asarray(got_c), want_ctrl)


def test_serving_expansion_with_tail_kernel(monkeypatch):
    """The covering-subtree expansion served in tail mode (fused last
    levels + value hash, interpret mode) is bit-identical to the limb
    kernel — exercising the tiled-order exit permutation."""
    import functools

    from distributed_point_functions_tpu.pir import dense_eval_planes as dep
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.dense_eval import (
        evaluate_selection_blocks,
        stage_keys,
    )

    monkeypatch.setattr(
        dep, "expand_level_planes_pallas",
        functools.partial(dep.expand_level_planes_pallas, interpret=True),
    )
    monkeypatch.setattr(
        dep, "expand_tail_planes_pallas",
        functools.partial(dep.expand_tail_planes_pallas, interpret=True),
    )
    monkeypatch.setattr(
        dep, "expand_head_planes_pallas",
        functools.partial(dep.expand_head_planes_pallas, interpret=True),
    )
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "tail")
    monkeypatch.setenv("DPF_TPU_TAIL_LEVELS", "2")
    # Tiny tiles so several tail calls + the cross-tile order run.
    monkeypatch.setenv("DPF_TPU_TAIL_TILE_LANES", "8")
    # Fused head over the first two levels: head -> per-level -> tail in
    # one serving program.
    monkeypatch.setenv("DPF_TPU_HEAD_LEVELS", "2")

    num_records = 19 * 128  # odd block count: exercises truncation
    nq = 64  # exact key-group multiple (kg 3 coverage lives in
    #          test_tail_kernel_matches_xla[12-96-2-6])
    num_blocks = (num_records + 127) // 128
    total = max(0, (num_records - 1).bit_length())
    expand = min((num_blocks - 1).bit_length(), total)
    walk = total - expand

    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    idx = [int(i) for i in RNG.integers(0, num_records, nq)]
    keys0, _ = client._generate_key_pairs(idx)
    staged = stage_keys(keys0)

    want = np.asarray(evaluate_selection_blocks(
        *staged, walk_levels=walk, expand_levels=expand,
        num_blocks=num_blocks,
    ))
    got = np.asarray(dep.evaluate_selection_blocks_planes(
        *staged, walk_levels=walk, expand_levels=expand,
        num_blocks=num_blocks, force_planes=True,
    ))
    np.testing.assert_array_equal(got, want)


def test_hierarchical_expansion_with_tail_kernel(monkeypatch):
    """Full-domain evaluate_next in tail mode (fused last levels + leaf
    hash per subtree tile, interpret mode) matches the limb program —
    exercising the tiled exit permutation with shared correction words
    (kg=1 planes) and the kernel's control-bit output."""
    import functools

    from distributed_point_functions_tpu import dpf as dpf_mod
    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.ops import (
        expand_planes_pallas as epp,
    )
    from distributed_point_functions_tpu.value_types import IntType

    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "limb")
    params = DpfParameters(log_domain_size=9, value_type=IntType(64))
    d = DistributedPointFunction.create(params)
    k0, k1 = d.generate_keys(400, 55)

    def run_both():
        outs = []
        for k in (k0, k1):
            ctx = d.create_evaluation_context(k)
            outs.append(np.asarray(d.evaluate_next([], ctx)))
        return outs

    want = run_both()

    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "tail")
    monkeypatch.setenv("DPF_TPU_TAIL_LEVELS", "2")
    monkeypatch.setenv("DPF_TPU_TAIL_TILE_LANES", "16")
    # Fused head over the first two plane levels: head -> per-level ->
    # tail in the one hierarchical program.
    monkeypatch.setenv("DPF_TPU_HEAD_LEVELS", "2")
    for name in ("expand_level_planes_pallas", "value_hash_planes_pallas",
                 "expand_tail_planes_pallas", "expand_head_planes_pallas"):
        monkeypatch.setattr(
            epp, name, functools.partial(getattr(epp, name), interpret=True)
        )
    dpf_mod._expand_levels_planes_fn.cache_clear()
    with warnings.catch_warnings():
        # The tail path must actually serve (no silent XLA fallback).
        warnings.simplefilter("error")
        got = run_both()
    dpf_mod._expand_levels_planes_fn.cache_clear()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)

    def u64(x):
        return (int(x[1]) << 32) | int(x[0])

    total = (u64(want[0][400]) + u64(want[1][400])) % (1 << 64)
    assert total == 55


def test_walk_descend_multi_tile():
    """Tile boundaries inside and across the 2^r leaf blocks must not
    change the result (per-lane descent is tile-local)."""
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        walk_descend_planes_pallas,
    )

    nk, r, kg, n_entry = 64, 2, 2, 2
    g0 = n_entry * kg
    state, ctrl, _, _, _ = _random_inputs(g0, nk)
    cwp_all = jnp.stack(
        [pack_key_planes(jnp.asarray(
            RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
        )) for _ in range(r)]
    )
    cwl_all = jnp.stack(
        [pack_key_bits(jnp.asarray(
            RNG.integers(0, 2, (nk,), dtype=np.uint32)
        )) for _ in range(r)]
    )
    cwr_all = jnp.stack(
        [pack_key_bits(jnp.asarray(
            RNG.integers(0, 2, (nk,), dtype=np.uint32)
        )) for _ in range(r)]
    )
    full, full_c = walk_descend_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_all, cwl_all,
        cwr_all, r=r, tile_lanes=g0 << r, interpret=True,
    )
    tiled, tiled_c = walk_descend_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_all, cwl_all,
        cwr_all, r=r, tile_lanes=kg * 4, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(full), np.asarray(tiled))
    np.testing.assert_array_equal(np.asarray(full_c), np.asarray(tiled_c))


@pytest.mark.parametrize(
    "expand_levels,head,tail,compact",
    [
        (4, 2, 2, False),  # walk head + walk tail, no middle
        (5, 2, 2, False),  # walk head + PER-LEVEL middle + walk tail:
        #                    the production composition at serving
        #                    shapes, where the leaf-order bookkeeping
        #                    appends doubling between two natural-order
        #                    walk phases
        (5, 2, 2, True),   # same, compact-entry mode (offset-major
        #                    tiles composed into the exit gather)
    ],
)
def test_walk_dispatch_integration(
    monkeypatch, expand_levels, head, tail, compact
):
    """The planes pipeline with walk-kind head+tail must be
    bit-identical to the XLA pipeline — exercises the leaf-order
    bookkeeping end to end."""
    import functools as ft

    from distributed_point_functions_tpu.ops import (
        expand_planes_pallas as epp,
    )
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    monkeypatch.setattr(
        dep, "walk_descend_planes_pallas",
        ft.partial(epp.walk_descend_planes_pallas, interpret=True),
    )
    monkeypatch.setattr(
        dep, "expand_level_planes_pallas",
        ft.partial(epp.expand_level_planes_pallas, interpret=True),
    )
    nk = 32
    num_blocks = 1 << expand_levels
    rng = np.random.default_rng(55)
    seeds0 = rng.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    control0 = rng.integers(0, 2, (nk,), dtype=np.uint32)
    cw_seeds = rng.integers(
        0, 1 << 32, (expand_levels, nk, 4), dtype=np.uint32
    )
    cw_left = rng.integers(0, 2, (expand_levels, nk), dtype=np.uint32)
    cw_right = rng.integers(0, 2, (expand_levels, nk), dtype=np.uint32)
    last_vc = rng.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    args = tuple(
        jnp.asarray(a)
        for a in (seeds0, control0, cw_seeds, cw_left, cw_right, last_vc)
    )
    kwargs = dict(
        walk_levels=0, expand_levels=expand_levels, num_blocks=num_blocks
    )
    want = np.asarray(
        dep._evaluate_selection_blocks_planes_jit(*args, **kwargs)
    )
    got = np.asarray(
        dep._evaluate_selection_blocks_planes_jit(
            *args, **kwargs,
            level_kernel=True,
            head_levels=head,
            tail_levels=tail,
            tail_kind="walk",
            head_kind="walk",
            walk_compact=compact,
        )
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tiles", [1, 2])
def test_walk_compact_entry_matches_replicated(tiles):
    """compact_entry reads the unreplicated entry per tile and exits
    offset-major; through walk_compact_leaf_order it must be
    bit-identical to the replicated natural-order mode."""
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        walk_compact_leaf_order,
        walk_descend_planes_pallas,
    )

    nk, r, kg = 64, 2, 2
    n_entry = 4
    g0 = n_entry * kg
    w = g0 << r
    tile = w // tiles
    state, ctrl, _, _, _ = _random_inputs(g0, nk)
    cwp_all = jnp.stack([
        pack_key_planes(jnp.asarray(
            RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
        )) for _ in range(r)
    ])
    cwl_all = jnp.stack([
        pack_key_bits(jnp.asarray(
            RNG.integers(0, 2, (nk,), dtype=np.uint32)
        )) for _ in range(r)
    ])
    cwr_all = jnp.stack([
        pack_key_bits(jnp.asarray(
            RNG.integers(0, 2, (nk,), dtype=np.uint32)
        )) for _ in range(r)
    ])
    vc = pack_key_planes(jnp.asarray(
        RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    ))
    nat_v, nat_c = walk_descend_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_all, cwl_all,
        cwr_all, vc, r=r, tile_lanes=w, value_hash=True, interpret=True,
    )
    got_v, got_c = walk_descend_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_all, cwl_all,
        cwr_all, vc, r=r, tile_lanes=tile, value_hash=True,
        compact_entry=True, interpret=True,
    )
    # Natural-mode output: leaf g at node-position g. Compact output:
    # position per walk_compact_leaf_order; gather compact -> natural.
    order = walk_compact_leaf_order(
        np.arange(n_entry), r, (tile >> r) // kg
    )
    pos_of_leaf = np.argsort(order)
    lanes = (
        pos_of_leaf[:, None] * kg + np.arange(kg)[None, :]
    ).reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(got_v)[:, :, lanes], np.asarray(nat_v)
    )
    np.testing.assert_array_equal(
        np.asarray(got_c)[lanes], np.asarray(nat_c)
    )




def test_tail_dispatch_odd_kg_matches_xla(monkeypatch):
    """Serving-side concat-tail dispatch at kg=3 with a non-power-of-two
    tile (tile % 8 != 0): the cross-tile exit-order composition and
    truncation at odd-kg geometry — the coverage the shrunken
    test_serving_expansion_with_tail_kernel (nq 64) no longer carries."""
    import functools as ft

    from distributed_point_functions_tpu.ops import (
        expand_planes_pallas as epp,
    )
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    for name in ("expand_level_planes_pallas", "expand_tail_planes_pallas"):
        monkeypatch.setattr(
            dep, name, ft.partial(getattr(dep, name), interpret=True)
        )
    nk, expand_levels = 96, 4  # kg=3
    num_blocks = 13  # odd, < 2^4: exercises truncation
    rng = np.random.default_rng(77)
    args = tuple(
        jnp.asarray(a)
        for a in (
            rng.integers(0, 1 << 32, (nk, 4), dtype=np.uint32),
            rng.integers(0, 2, (nk,), dtype=np.uint32),
            rng.integers(0, 1 << 32, (expand_levels, nk, 4),
                         dtype=np.uint32),
            rng.integers(0, 2, (expand_levels, nk), dtype=np.uint32),
            rng.integers(0, 2, (expand_levels, nk), dtype=np.uint32),
            rng.integers(0, 1 << 32, (nk, 4), dtype=np.uint32),
        )
    )
    kwargs = dict(
        walk_levels=0, expand_levels=expand_levels, num_blocks=num_blocks
    )
    want = np.asarray(
        dep._evaluate_selection_blocks_planes_jit(*args, **kwargs)
    )
    got = np.asarray(
        dep._evaluate_selection_blocks_planes_jit(
            *args, **kwargs,
            level_kernel=True, tail_levels=2, tail_tile_nodes=2,
        )
    )
    np.testing.assert_array_equal(got, want)
