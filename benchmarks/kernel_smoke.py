"""Tiny-shape hardware smoke of every new Pallas kernel (fast compiles).

One JSON line per kernel with ok/fail + compile seconds + bit-identity
vs the XLA twin, so one short chip call tells which kernels Mosaic
accepts on this hardware.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root
import time

import numpy as np


def log(msg):
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def check(name, fn):
    t0 = time.perf_counter()
    try:
        fn()
        line = {"kernel": name, "ok": True,
                "compile_s": round(time.perf_counter() - t0, 1)}
    except Exception as e:  # noqa: BLE001
        line = {"kernel": name, "ok": False,
                "error": str(e).splitlines()[0][:300]}
    print(json.dumps(line), flush=True)


def main():
    import jax
    import jax.numpy as jnp

    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    log(f"devices: {jax.devices()}")

    from distributed_point_functions_tpu.ops.inner_product import (
        xor_inner_product,
        pack_selection_bits_np,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
        xor_inner_product_pallas2_staged,
        xor_inner_product_pallas_staged,
    )

    rng = np.random.default_rng(3)
    db = jnp.asarray(rng.integers(0, 1 << 32, (8192, 8), dtype=np.uint32))
    bits = rng.integers(0, 2, (8, 8192), dtype=np.uint32)
    sel = jnp.asarray(pack_selection_bits_np(bits))
    db_perm = permute_db_bitmajor(db)
    want_ip = np.asarray(xor_inner_product(db, sel))

    def smoke_ip(fn, **kw):
        got = np.asarray(fn(db_perm, sel, **kw))
        assert np.array_equal(got, want_ip), "bit mismatch vs jnp"

    check("ip_pallas_v1", lambda: smoke_ip(xor_inner_product_pallas_staged))
    check("ip_pallas2_int8",
          lambda: smoke_ip(xor_inner_product_pallas2_staged, int8=True))
    check("ip_pallas2_bf16",
          lambda: smoke_ip(xor_inner_product_pallas2_staged, int8=False))

    # Level kernels vs XLA twins.
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        expand_level_planes_pallas,
        value_hash_planes_pallas,
    )
    from distributed_point_functions_tpu import keys as fixed_keys
    from distributed_point_functions_tpu.ops.aes_bitslice import (
        mmo_hash_planes,
        pack_select_bits,
    )
    from distributed_point_functions_tpu.pir.dense_eval_planes import (
        _tile_keys,
        expand_level_planes,
        pack_key_bits,
        pack_key_planes,
    )

    g, nk = 64, 64
    kgp = pack_key_planes(
        jnp.asarray(rng.integers(0, 1 << 32, (nk, 4), dtype=np.uint32))
    )
    kgl = pack_key_bits(
        jnp.asarray(rng.integers(0, 2, (nk,), dtype=np.uint32))
    )
    kgr = pack_key_bits(
        jnp.asarray(rng.integers(0, 2, (nk,), dtype=np.uint32))
    )
    state = jnp.asarray(
        rng.integers(0, 1 << 32, (16, 8, g), dtype=np.uint32)
    )
    ctrl = jnp.asarray(rng.integers(0, 1 << 32, (g,), dtype=np.uint32))

    def smoke_level():
        want_s, want_c = expand_level_planes(
            state, ctrl, _tile_keys(kgp, 2 * g), _tile_keys(kgl, g),
            _tile_keys(kgr, g),
        )
        got_s, got_c = expand_level_planes_pallas(
            state, ctrl, kgp, kgl, kgr
        )
        assert np.array_equal(np.asarray(got_s), np.asarray(want_s))
        assert np.array_equal(np.asarray(got_c), np.asarray(want_c))

    check("level_expand_pallas", smoke_level)

    def smoke_value():
        want = mmo_hash_planes(fixed_keys.RK_VALUE, state) ^ (
            _tile_keys(kgp, g) & ctrl[None, None, :]
        )
        got = value_hash_planes_pallas(state, ctrl, kgp)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    check("value_hash_pallas", smoke_value)

    def smoke_path():
        from distributed_point_functions_tpu import dpf as dpf_mod

        sel_bits = pack_select_bits(
            jnp.asarray(rng.integers(0, 2, (32 * g,), dtype=np.uint32))
        )
        # Differential via the full walk (shared-cw mode, one level).
        n = 32 * g
        seeds = jnp.asarray(
            rng.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
        )
        control = jnp.asarray(rng.integers(0, 2, (n,), dtype=np.uint32))
        paths = jnp.asarray(
            rng.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
        )
        cw_seeds = jnp.asarray(
            rng.integers(0, 1 << 32, (2, 1, 4), dtype=np.uint32)
        )
        cw_l = jnp.asarray(rng.integers(0, 2, (2, 1), dtype=np.uint32))
        cw_r = jnp.asarray(rng.integers(0, 2, (2, 1), dtype=np.uint32))
        bidx = jnp.asarray(np.array([1, 0], dtype=np.uint32))
        want = dpf_mod._eval_paths_limb(
            seeds, control, paths, cw_seeds, cw_l, cw_r, bidx
        )
        got = dpf_mod._eval_paths_planes(
            seeds, control, paths, cw_seeds, cw_l, cw_r, bidx,
            level_kernel=True,
        )
        for w, gg in zip(want, got):
            assert np.array_equal(np.asarray(gg), np.asarray(w))
        del sel_bits

    check("path_level_pallas", smoke_path)

    def smoke_walk(unroll):
        from distributed_point_functions_tpu.ops.expand_planes_pallas import (
            tail_node_permutation,
            walk_descend_planes_pallas,
        )

        nk, r = 64, 2
        kg = nk // 32
        g0 = 4 * kg
        st = jnp.asarray(
            rng.integers(0, 1 << 32, (16, 8, g0), dtype=np.uint32)
        )
        ct = jnp.asarray(
            rng.integers(0, 1 << 32, (g0,), dtype=np.uint32)
        )
        cwp = jnp.asarray(
            rng.integers(0, 1 << 32, (r, 16, 8, kg), dtype=np.uint32)
        )
        cwl = jnp.asarray(
            rng.integers(0, 1 << 32, (r, kg), dtype=np.uint32)
        )
        cwr = jnp.asarray(
            rng.integers(0, 1 << 32, (r, kg), dtype=np.uint32)
        )
        vc = jnp.asarray(
            rng.integers(0, 1 << 32, (16, 8, kg), dtype=np.uint32)
        )
        s, c = st, ct
        for i in range(r):
            g2 = 2 * s.shape[-1]
            s, c = expand_level_planes(
                s, c, _tile_keys(cwp[i], g2), _tile_keys(cwl[i], g2 // 2),
                _tile_keys(cwr[i], g2 // 2),
            )
        want = mmo_hash_planes(fixed_keys.RK_VALUE, s) ^ (
            _tile_keys(vc, s.shape[-1]) & c[None, None, :]
        )
        n_entry = g0 // kg
        pos_of_leaf = tail_node_permutation(
            np.arange(n_entry), r, n_entry
        )[1]
        lanes = (
            pos_of_leaf[:, None] * kg + np.arange(kg)[None, :]
        ).reshape(-1)
        got_v, got_c = walk_descend_planes_pallas(
            st, ct, cwp, cwl, cwr, vc, r=r, tile_lanes=g0 << r,
            value_hash=True, unroll=unroll,
        )
        assert np.array_equal(
            np.asarray(got_v), np.asarray(want)[:, :, lanes]
        )
        assert np.array_equal(np.asarray(got_c), np.asarray(c)[lanes])

    check("walk_descend_pallas", lambda: smoke_walk(True))
    check("walk_descend_pallas_loop", lambda: smoke_walk(False))


if __name__ == "__main__":
    main()
