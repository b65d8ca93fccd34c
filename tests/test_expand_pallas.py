"""Differential tests for the Pallas plane-expansion kernels (interpret
mode on CPU) against their XLA twins in `pir/dense_eval_planes.py` —
the same per-target discipline as the inner-product kernels
(`pir/internal/inner_product_hwy_test.cc:427-434`)."""

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


@pytest.mark.parametrize("g,nk", [(8, 32), (64, 64), (24, 96)])
def test_level_kernel_matches_xla(g, nk):
    state, ctrl, cw, cwl, cwr = _random_inputs(g, nk)
    cwp_kg = pack_key_planes(jnp.asarray(cw))
    cwl_kg = pack_key_bits(jnp.asarray(cwl))
    cwr_kg = pack_key_bits(jnp.asarray(cwr))

    want_state, want_ctrl = jax.jit(expand_level_planes)(
        jnp.asarray(state),
        jnp.asarray(ctrl),
        _tile_keys(cwp_kg, 2 * g),
        _tile_keys(cwl_kg, g),
        _tile_keys(cwr_kg, g),
    )
    got_state, got_ctrl = expand_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_kg, cwl_kg, cwr_kg,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_state),
                                  np.asarray(want_state))
    np.testing.assert_array_equal(np.asarray(got_ctrl),
                                  np.asarray(want_ctrl))


@pytest.mark.parametrize("tile", [16, 32, 128])
def test_level_kernel_chunked_matches_xla(tile):
    """Forced sub-width lane tiles exercise the chunked path (one
    grid-(1,) pallas_call per lane slice — multi-step lane grids crash
    tpu_compile_helper on v5e) and must keep the global
    [all-left; all-right] child order. tile=128 > g covers a chunk
    narrower than the nominal tile (the in-kernel repeat factor must
    follow the chunk width, not the tile)."""
    g, nk = 64, 64
    state, ctrl, cw, cwl, cwr = _random_inputs(g, nk)
    cwp_kg = pack_key_planes(jnp.asarray(cw))
    cwl_kg = pack_key_bits(jnp.asarray(cwl))
    cwr_kg = pack_key_bits(jnp.asarray(cwr))

    want_state, want_ctrl = expand_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_kg, cwl_kg, cwr_kg,
        interpret=True,
    )
    got_state, got_ctrl = expand_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_kg, cwl_kg, cwr_kg,
        interpret=True, tile_lanes=tile,
    )
    np.testing.assert_array_equal(np.asarray(got_state),
                                  np.asarray(want_state))
    np.testing.assert_array_equal(np.asarray(got_ctrl),
                                  np.asarray(want_ctrl))


def test_value_kernel_chunked_matches_xla():
    g, nk = 64, 64
    state, ctrl, cw, _, _ = _random_inputs(g, nk)
    vc_kg = pack_key_planes(jnp.asarray(cw))

    want = value_hash_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), vc_kg, interpret=True
    )
    got = value_hash_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), vc_kg, interpret=True,
        tile_lanes=16,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("per_seed", [False, True])
def test_path_kernel_chunked_matches_unchunked(per_seed):
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        path_level_planes_pallas,
    )

    g, nk = 64, 64
    state, ctrl, cw, cwl, cwr = _random_inputs(g, nk)
    sel = RNG.integers(0, 1 << 32, (g,), dtype=np.uint32)
    if per_seed:
        cwp = jnp.asarray(
            RNG.integers(0, 1 << 32, (16, 8, g), dtype=np.uint32)
        )
        cwlb = jnp.asarray(RNG.integers(0, 1 << 32, (g,), dtype=np.uint32))
        cwrb = jnp.asarray(RNG.integers(0, 1 << 32, (g,), dtype=np.uint32))
    else:
        cwp = pack_key_planes(jnp.asarray(cw))
        cwlb = pack_key_bits(jnp.asarray(cwl))
        cwrb = pack_key_bits(jnp.asarray(cwr))

    want = path_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), jnp.asarray(sel),
        cwp, cwlb, cwrb, per_seed, interpret=True,
    )
    got = path_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), jnp.asarray(sel),
        cwp, cwlb, cwrb, per_seed, interpret=True, tile_lanes=16,
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("g,nk", [(2, 64), (64, 64), (24, 96)])
def test_value_kernel_matches_xla(g, nk):
    state, ctrl, cw, _, _ = _random_inputs(g, nk)
    vc_kg = pack_key_planes(jnp.asarray(cw))

    want = mmo_hash_planes(fixed_keys.RK_VALUE, jnp.asarray(state))
    want = want ^ (
        _tile_keys(vc_kg, g) & jnp.asarray(ctrl)[None, None, :]
    )
    got = value_hash_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), vc_kg, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_serving_expansion_with_level_kernel(monkeypatch):
    """The full covering-subtree expansion served through the Pallas
    level kernels (interpret mode) is bit-identical to the limb kernel."""
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
        dep, "value_hash_planes_pallas",
        functools.partial(dep.value_hash_planes_pallas, interpret=True),
    )
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "pallas")

    num_records = 9 * 128  # odd block count: exercises truncation
    nq = 32
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


def test_hierarchical_expansion_with_level_kernel(monkeypatch):
    """Full-domain evaluate_next through the plane path with the Pallas
    level kernel (interpret mode) matches the limb program."""
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
    k0, k1 = d.generate_keys(300, 99)

    def run_both():
        outs = []
        for k in (k0, k1):
            ctx = d.create_evaluation_context(k)
            outs.append(np.asarray(d.evaluate_next([], ctx)))
        return outs

    want = run_both()

    # Planes path + forced Pallas level kernel, interpret mode: patch the
    # kernel symbol where the planes program imports it from.
    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "pallas")
    monkeypatch.setattr(
        epp, "expand_level_planes_pallas",
        functools.partial(epp.expand_level_planes_pallas, interpret=True),
    )
    monkeypatch.setattr(
        epp, "value_hash_planes_pallas",
        functools.partial(epp.value_hash_planes_pallas, interpret=True),
    )
    dpf_mod._expand_levels_planes_fn.cache_clear()
    with warnings.catch_warnings():
        # The kernel path must actually serve (no silent XLA fallback).
        warnings.simplefilter("error")
        got = run_both()
    dpf_mod._expand_levels_planes_fn.cache_clear()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    # uint64 values are (lo, hi) uint32 limb pairs on CPU (no x64).
    def u64(x):
        return (int(x[1]) << 32) | int(x[0])

    total = (u64(want[0][300]) + u64(want[1][300])) % (1 << 64)
    assert total == 99


@pytest.mark.parametrize("per_seed", [False, True])
def test_path_walk_with_level_kernel(monkeypatch, per_seed):
    """The path walk served through the Pallas select-key kernel
    (interpret mode) matches the limb walk in both correction modes."""
    import functools

    from distributed_point_functions_tpu import dpf as dpf_mod
    from distributed_point_functions_tpu.ops import (
        expand_planes_pallas as epp,
    )

    monkeypatch.setattr(
        epp, "path_level_planes_pallas",
        functools.partial(epp.path_level_planes_pallas, interpret=True),
    )

    n, levels = 64, 6
    seeds = RNG.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
    control = RNG.integers(0, 2, (n,), dtype=np.uint32)
    paths = RNG.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
    m = n if per_seed else 1
    cw_seeds = RNG.integers(0, 1 << 32, (levels, m, 4), dtype=np.uint32)
    cw_left = RNG.integers(0, 2, (levels, m), dtype=np.uint32)
    cw_right = RNG.integers(0, 2, (levels, m), dtype=np.uint32)
    bit_indices = np.arange(levels, dtype=np.uint32)[::-1].copy()

    args = tuple(
        jnp.asarray(a)
        for a in (seeds, control, paths, cw_seeds, cw_left, cw_right,
                  bit_indices)
    )
    want_s, want_c = dpf_mod._eval_paths_limb(*args)
    got_s, got_c = dpf_mod._eval_paths_planes(*args, level_kernel=True)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))


def test_hierarchical_fused_leaf_hash_planes_xla(monkeypatch):
    """The fused leaf value hash (hash_leaves) in the XLA planes program
    matches the limb program (which fuses the same hash)."""
    from distributed_point_functions_tpu import dpf as dpf_mod
    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.value_types import IntType

    params = DpfParameters(log_domain_size=8, value_type=IntType(32))
    d = DistributedPointFunction.create(params)
    k0, k1 = d.generate_keys(213, 7)

    def run_both():
        outs = []
        for k in (k0, k1):
            ctx = d.create_evaluation_context(k)
            outs.append(np.asarray(d.evaluate_next([], ctx)))
        return outs

    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "limb")
    want = run_both()
    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "xla")
    got = run_both()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
    total = (want[0].astype(np.uint64) + want[1].astype(np.uint64))
    assert int(total[213].item()) % (1 << 32) == 7


def test_level_kernel_selfcheck(monkeypatch):
    """Auto mode bit-checks its kernel set once on TPU; a kernel that
    returns garbage raises — the XLA levels never stand in for it."""
    import functools

    from distributed_point_functions_tpu.ops import (
        expand_planes_pallas as epp,
    )
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", "auto")
    monkeypatch.setattr(dep.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dep, "_LEVEL_KERNEL_VERIFIED", False)
    for name in ("expand_level_planes_pallas", "value_hash_planes_pallas",
                 "path_level_planes_pallas"):
        monkeypatch.setattr(
            epp, name, functools.partial(getattr(epp, name), interpret=True)
        )
    assert dep._level_kernel_enabled() == dep.TPU_AUTO_LEVEL_KERNEL
    assert dep._LEVEL_KERNEL_VERIFIED is True

    monkeypatch.setattr(dep, "_LEVEL_KERNEL_VERIFIED", False)
    good = epp.expand_level_planes_pallas

    def bad(state, ctrl, cwp, cwl, cwr, **kw):
        s, c = good(state, ctrl, cwp, cwl, cwr)
        return s ^ jnp.uint32(1), c

    monkeypatch.setattr(epp, "expand_level_planes_pallas", bad)
    with pytest.raises(RuntimeError, match="bit mismatch"):
        dep._level_kernel_enabled()
    assert dep._LEVEL_KERNEL_VERIFIED is False
