"""Fast-tier Pallas kernel signal: tiny-shape interpret-mode differential
cases of every serving kernel family (level / path / tail / v2 inner
product), sized for the presubmit's <3 min budget.

The full differential sweeps live in `tests/test_expand_pallas.py` and
`tests/test_pallas.py`; this module exists because a presubmit whose fast
tier skips every Pallas kernel is blind to the code the serving path
actually runs (VERDICT r03). Twins are jitted — an eager bitsliced-AES
twin pays thousands of per-op CPU dispatches and would blow the budget.
"""

import functools

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
    expand_tail_planes_pallas,
    path_level_planes_pallas,
    value_hash_planes_pallas,
)
from distributed_point_functions_tpu.pir.dense_eval_planes import (
    _tile_keys,
    expand_level_planes,
    pack_key_bits,
    pack_key_planes,
)

RNG = np.random.default_rng(71)


def _inputs(g, nk):
    state = RNG.integers(0, 1 << 32, (16, 8, g), dtype=np.uint32)
    ctrl = RNG.integers(0, 1 << 32, (g,), dtype=np.uint32)
    cw = RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    cwl = RNG.integers(0, 2, (nk,), dtype=np.uint32)
    cwr = RNG.integers(0, 2, (nk,), dtype=np.uint32)
    return state, ctrl, cw, cwl, cwr


def test_level_kernel_tiny():
    g, nk = 2, 64
    state, ctrl, cw, cwl, cwr = _inputs(g, nk)
    cwp_kg = pack_key_planes(jnp.asarray(cw))
    cwl_kg = pack_key_bits(jnp.asarray(cwl))
    cwr_kg = pack_key_bits(jnp.asarray(cwr))
    want_s, want_c = jax.jit(expand_level_planes)(
        jnp.asarray(state), jnp.asarray(ctrl),
        _tile_keys(cwp_kg, 2 * g), _tile_keys(cwl_kg, g),
        _tile_keys(cwr_kg, g),
    )
    got_s, got_c = expand_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_kg, cwl_kg, cwr_kg,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))


def test_value_kernel_tiny():
    g, nk = 2, 64
    state, ctrl, cw, _, _ = _inputs(g, nk)
    vc_kg = pack_key_planes(jnp.asarray(cw))

    @jax.jit
    def twin(state, ctrl, vc):
        out = mmo_hash_planes(fixed_keys.RK_VALUE, state)
        return out ^ (_tile_keys(vc, g) & ctrl[None, None, :])

    want = twin(jnp.asarray(state), jnp.asarray(ctrl), vc_kg)
    got = value_hash_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), vc_kg, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_path_kernel_tiny():
    from distributed_point_functions_tpu.ops.aes_bitslice import (
        aes_rounds_select_planes,
        sigma_planes,
    )

    g, nk = 2, 64
    state, ctrl, cw, cwl, cwr = _inputs(g, nk)
    sel = RNG.integers(0, 1 << 32, (g,), dtype=np.uint32)
    cwp = pack_key_planes(jnp.asarray(cw))
    cwlb = pack_key_bits(jnp.asarray(cwl))
    cwrb = pack_key_bits(jnp.asarray(cwr))

    @jax.jit
    def twin(state, ctrl, sel, cwp, cwlb, cwrb):
        sig = sigma_planes(state)
        h = aes_rounds_select_planes(
            fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, sel, sig
        ) ^ sig
        h = h ^ (_tile_keys(cwp, g) & ctrl[None, None, :])
        t_new = h[0, 0]
        out_s = h.at[0, 0].set(jnp.zeros_like(t_new))
        cw_dir = (sel & _tile_keys(cwrb, g)) | (~sel & _tile_keys(cwlb, g))
        return out_s, t_new ^ (ctrl & cw_dir)

    want_s, want_c = twin(
        jnp.asarray(state), jnp.asarray(ctrl), jnp.asarray(sel),
        cwp, cwlb, cwrb,
    )
    got_s, got_c = path_level_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), jnp.asarray(sel),
        cwp, cwlb, cwrb, per_seed=False, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))


def test_tail_kernel_tiny():
    """One fused tail level + value hash over two tiles — the multi-tile
    assembly and the in-kernel doubling at minimum interpret cost."""
    g0, nk, r, tile = 4, 32, 1, 2
    state, ctrl, cw, cwl, cwr = _inputs(g0, nk)
    cwp_kg = pack_key_planes(jnp.asarray(cw))[None]
    cwl_kg = pack_key_bits(jnp.asarray(cwl))[None]
    cwr_kg = pack_key_bits(jnp.asarray(cwr))[None]
    vc = RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    vc_kg = pack_key_planes(jnp.asarray(vc))

    @functools.partial(jax.jit, static_argnames=("lo",))
    def twin_tile(state, ctrl, cwp, cwlb, cwrb, vc, lo):
        s = jax.lax.slice_in_dim(state, lo, lo + tile, axis=2)
        c = jax.lax.slice_in_dim(ctrl, lo, lo + tile)
        s, c = expand_level_planes(
            s, c, _tile_keys(cwp[0], 2 * tile), _tile_keys(cwlb[0], tile),
            _tile_keys(cwrb[0], tile),
        )
        v = mmo_hash_planes(fixed_keys.RK_VALUE, s) ^ (
            _tile_keys(vc, s.shape[-1]) & c[None, None, :]
        )
        return v, c

    want_v, want_c = [], []
    for lo in range(0, g0, tile):
        v, c = twin_tile(
            jnp.asarray(state), jnp.asarray(ctrl), cwp_kg, cwl_kg,
            cwr_kg, vc_kg, lo,
        )
        want_v.append(np.asarray(v))
        want_c.append(np.asarray(c))
    got_v, got_c = expand_tail_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_kg, cwl_kg, cwr_kg,
        vc_kg, tile_lanes=tile, interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(got_v), np.concatenate(want_v, axis=-1)
    )
    np.testing.assert_array_equal(
        np.asarray(got_c), np.concatenate(want_c)
    )


def test_head_kernel_tiny():
    """The fused head (first r levels, one launch) is bit-identical to
    sequential XLA levels — no exit permutation, single tile."""
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        expand_head_planes_pallas,
    )

    g0, nk, r = 2, 64, 2
    state, ctrl, _, _, _ = _inputs(g0, nk)
    cwp = [
        pack_key_planes(jnp.asarray(
            RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
        ))
        for _ in range(r)
    ]
    cwl = [
        pack_key_bits(jnp.asarray(
            RNG.integers(0, 2, (nk,), dtype=np.uint32)
        ))
        for _ in range(r)
    ]
    cwr = [
        pack_key_bits(jnp.asarray(
            RNG.integers(0, 2, (nk,), dtype=np.uint32)
        ))
        for _ in range(r)
    ]

    @jax.jit
    def twin(s, c, cwp_st, cwl_st, cwr_st):
        for i in range(r):
            g2 = 2 * s.shape[-1]
            s, c = expand_level_planes(
                s, c, _tile_keys(cwp_st[i], g2),
                _tile_keys(cwl_st[i], g2 // 2),
                _tile_keys(cwr_st[i], g2 // 2),
            )
        return s, c

    want_s, want_c = twin(
        jnp.asarray(state), jnp.asarray(ctrl), jnp.stack(cwp),
        jnp.stack(cwl), jnp.stack(cwr),
    )
    got_s, got_c = expand_head_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), jnp.stack(cwp),
        jnp.stack(cwl), jnp.stack(cwr), interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))


def test_head_split_policy(monkeypatch):
    """_head_split: DPF_TPU_HEAD_LEVELS forces the count (clamped to the
    available levels); otherwise only walk mode has a head, filled to
    the 2048-lane cap (kg=4 -> 9 levels, at least 2 levels)."""
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    assert dep._head_split(4, 13) == 0
    assert dep._head_split(4, 13, walk=True) == 9
    assert dep._head_split(4, 2, walk=True) == 2
    assert dep._head_split(2048, 5, walk=True) == 0  # cap below 2 levels
    monkeypatch.setenv("DPF_TPU_HEAD_LEVELS", "6")
    assert dep._head_split(4, 13) == 6
    assert dep._head_split(4, 3) == 3


@pytest.mark.parametrize(
    "env,backend,want",
    [
        ("auto", "cpu", False),
        ("auto", "tpu", "pallas"),
        ("xla", "tpu", False),
        ("pallas", "cpu", "pallas"),
        ("tail", "cpu", "tail"),
        ("walk", "tpu", "walk"),
    ],
)
def test_level_kernel_mode_resolution(monkeypatch, env, backend, want):
    """Auto mode serves the fixed TPU kernel set on TPU and the XLA
    levels elsewhere; forced modes are taken as given."""
    from distributed_point_functions_tpu.pir import dense_eval_planes as dep

    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", env)
    monkeypatch.setattr(dep.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(dep, "_LEVEL_KERNEL_VERIFIED", True)
    assert dep._level_kernel_enabled() == want
    if want == "pallas" and env == "auto":
        assert want == dep.TPU_AUTO_LEVEL_KERNEL


@pytest.mark.parametrize("num_words", [2, 16])
def test_ip_v2_tiny(num_words):
    """v2 MXU inner product at a narrow (j_chunk=1 regression shape) and
    a regular width."""
    from distributed_point_functions_tpu.ops.inner_product import (
        pack_selection_bits_np,
        xor_inner_product_np,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
        xor_inner_product_pallas2_staged,
    )

    num_records, nq = 512, 4
    db = RNG.integers(0, 1 << 32, (num_records, num_words), dtype=np.uint32)
    bits = RNG.integers(0, 2, (nq, num_records), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    db_perm = np.asarray(permute_db_bitmajor(db))
    got = np.asarray(
        xor_inner_product_pallas2_staged(db_perm, sel, interpret=True)
    )
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))



@pytest.mark.parametrize(
    "value_hash,unroll", [(True, True), (False, False)]
)
def test_walk_descend_kernel_tiny(value_hash, unroll):
    """Fixed-width walk-descent vs the doubling expansion: 2 levels from
    2 entry nodes, natural leaf order (the doubling twin's [all-left;
    all-right] order is mapped through tail_node_permutation)."""
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        tail_node_permutation,
        walk_descend_planes_pallas,
    )

    nk, r = 32, 2
    kg = 1
    n_entry = 2
    g0 = n_entry * kg
    state, ctrl, cw, cwl, cwr = _inputs(g0, nk)
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
    vc = pack_key_planes(jnp.asarray(
        RNG.integers(0, 1 << 32, (nk, 4), dtype=np.uint32)
    ))

    @jax.jit
    def twin_doubling(state, ctrl):
        s, c = jnp.asarray(state), jnp.asarray(ctrl)
        for i in range(r):
            g2 = 2 * s.shape[-1]
            s, c = expand_level_planes(
                s, c, _tile_keys(cwp_all[i], g2),
                _tile_keys(cwl_all[i], g2 // 2),
                _tile_keys(cwr_all[i], g2 // 2),
            )
        if value_hash:
            s = mmo_hash_planes(fixed_keys.RK_VALUE, s) ^ (
                _tile_keys(vc, s.shape[-1]) & c[None, None, :]
            )
        return s, c

    want_s, want_c = twin_doubling(state, ctrl)
    # Map the doubling twin's global [all-left; all-right] node order to
    # the walk kernel's natural leaf order.
    order, _ = tail_node_permutation(np.arange(n_entry), r, n_entry)
    pos_of_leaf = np.argsort(order)
    lane_gather = (
        pos_of_leaf[:, None] * kg + np.arange(kg)[None, :]
    ).reshape(-1)
    want_s = np.asarray(want_s)[:, :, lane_gather]
    want_c = np.asarray(want_c)[lane_gather]

    got_s, got_c = walk_descend_planes_pallas(
        jnp.asarray(state), jnp.asarray(ctrl), cwp_all, cwl_all,
        cwr_all, vc if value_hash else None, r=r,
        tile_lanes=g0 << r, value_hash=value_hash, unroll=unroll,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_s), want_s)
    np.testing.assert_array_equal(np.asarray(got_c), want_c)
