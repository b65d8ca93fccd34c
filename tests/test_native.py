"""Differential tests: native C++ kernels vs numpy oracle vs JAX kernels.

The three-way check mirrors the reference's per-target SIMD-vs-scalar
differential testing (`dpf/internal/evaluate_prg_hwy_test.cc:49-136`,
`pir/internal/inner_product_hwy_test.cc:427-434`): identical inputs through
every implementation, outputs must be bit-identical.
"""

import numpy as np
import pytest

from distributed_point_functions_tpu import keys as fixed_keys
from distributed_point_functions_tpu import native
from distributed_point_functions_tpu.ops import aes
from distributed_point_functions_tpu.ops.inner_product import (
    pack_selection_bits_np,
    xor_inner_product,
    xor_inner_product_np,
)

RNG = np.random.default_rng(5)


def random_blocks(n):
    return RNG.integers(0, 256, (n, 16), dtype=np.uint8)


@pytest.mark.parametrize("which,rk", [
    (0, "RK_LEFT"), (1, "RK_RIGHT"), (2, "RK_VALUE"),
])
def test_native_mmo_hash_matches_oracle(which, rk):
    blocks = random_blocks(65)
    got = native.mmo_hash(which, blocks)
    limbs = aes.bytes_to_limbs_np(blocks)
    want = aes.limbs_to_bytes_np(
        aes.mmo_hash_np(getattr(fixed_keys, rk), limbs)
    )
    np.testing.assert_array_equal(got, want)


def test_native_expand_level_matches_jax():
    import jax.numpy as jnp

    from distributed_point_functions_tpu.dpf import _expand_level

    n = 17
    seeds = random_blocks(n)
    control = RNG.integers(0, 2, (n,), dtype=np.uint8)
    cw_seed = random_blocks(1)[0]
    cw_left, cw_right = 1, 0

    got_seeds, got_control = native.expand_level(
        seeds, control, cw_seed, cw_left, cw_right
    )

    limbs = aes.bytes_to_limbs_np(seeds)
    cw_limbs = aes.bytes_to_limbs_np(cw_seed[None])[0]
    jax_seeds, jax_control = _expand_level(
        jnp.asarray(limbs),
        jnp.asarray(control.astype(np.uint32)),
        jnp.asarray(cw_limbs),
        jnp.uint32(cw_left),
        jnp.uint32(cw_right),
    )
    np.testing.assert_array_equal(
        got_seeds, aes.limbs_to_bytes_np(np.asarray(jax_seeds))
    )
    np.testing.assert_array_equal(
        got_control, np.asarray(jax_control).astype(np.uint8)
    )


@pytest.mark.parametrize("per_seed_cw", [False, True])
def test_native_evaluate_seeds_matches_jax(per_seed_cw):
    import jax.numpy as jnp

    from distributed_point_functions_tpu.dpf import _eval_paths

    n, levels = 9, 6
    seeds = random_blocks(n)
    control = RNG.integers(0, 2, (n,), dtype=np.uint8)
    paths = np.zeros((n, 16), dtype=np.uint8)
    paths[:, 0] = RNG.integers(0, 64, n)  # 6-bit paths
    m = n if per_seed_cw else 1
    cw_seeds = RNG.integers(0, 256, (levels, m, 16), dtype=np.uint8)
    cw_left = RNG.integers(0, 2, (levels, m), dtype=np.uint8)
    cw_right = RNG.integers(0, 2, (levels, m), dtype=np.uint8)

    got_seeds, got_control = native.evaluate_seeds(
        seeds, control, paths, cw_seeds, cw_left, cw_right, per_seed_cw
    )

    bit_indices = np.array(
        [levels - 1 - j for j in range(levels)], dtype=np.int32
    )
    jax_seeds, jax_control = _eval_paths(
        jnp.asarray(aes.bytes_to_limbs_np(seeds)),
        jnp.asarray(control.astype(np.uint32)),
        jnp.asarray(aes.bytes_to_limbs_np(paths)),
        jnp.asarray(aes.bytes_to_limbs_np(cw_seeds)),
        jnp.asarray(cw_left.astype(np.uint32)),
        jnp.asarray(cw_right.astype(np.uint32)),
        jnp.asarray(bit_indices),
    )
    np.testing.assert_array_equal(
        got_seeds, aes.limbs_to_bytes_np(np.asarray(jax_seeds))
    )
    np.testing.assert_array_equal(
        got_control, np.asarray(jax_control).astype(np.uint8)
    )


def test_native_value_hash_matches_jax():
    from distributed_point_functions_tpu.dpf import _value_hash

    seeds = random_blocks(5)
    got = native.value_hash(seeds, 3)
    jax_out = np.asarray(
        _value_hash(aes.bytes_to_limbs_np(seeds), 3)
    )  # [n, B, 4]
    want = aes.limbs_to_bytes_np(jax_out)
    np.testing.assert_array_equal(got, want)


def test_native_inner_product_matches_oracles():
    num_records, num_words, nq = 384, 10, 3
    db = RNG.integers(0, 1 << 32, (num_records, num_words), dtype=np.uint32)
    bits = RNG.integers(0, 2, (nq, num_records), dtype=np.uint32)
    packed = pack_selection_bits_np(bits)  # [nq, B, 4] uint32
    sel_bytes = np.ascontiguousarray(packed.astype("<u4")).view(np.uint8)
    sel_bytes = sel_bytes.reshape(nq, -1, 16)

    got = native.inner_product(db, sel_bytes)
    want_np = xor_inner_product_np(db, packed)
    want_jax = np.asarray(xor_inner_product(db, packed))
    np.testing.assert_array_equal(got, want_np)
    np.testing.assert_array_equal(got, want_jax)


def test_native_keygen_batch_matches_numpy(monkeypatch):
    """The C++ AES-NI batch keygen (`native/keygen.cc`) must be
    bit-identical to the numpy engine on the same injected root seeds."""
    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.value_types import XorType

    dpf = DistributedPointFunction.create(
        DpfParameters(log_domain_size=9, value_type=XorType(128))
    )
    rng = np.random.default_rng(31)
    n = 13
    alphas = [int(a) for a in rng.integers(0, 512, n)]
    betas = [1 << int(b) for b in rng.integers(0, 128, n)]
    seeds = rng.integers(0, 1 << 32, (2, n, 4), dtype=np.uint32)

    monkeypatch.setenv("DPF_NATIVE_KEYGEN", "1")
    nat0, nat1 = dpf.generate_keys_batch(alphas, betas, _root_seeds=seeds)
    monkeypatch.setenv("DPF_NATIVE_KEYGEN", "0")
    py0, py1 = dpf.generate_keys_batch(alphas, betas, _root_seeds=seeds)

    for a, b in zip(nat0 + nat1, py0 + py1):
        assert a.seed == b.seed and a.party == b.party
        assert a.last_level_value_correction == b.last_level_value_correction
        for ca, cb in zip(a.correction_words, b.correction_words):
            assert ca.seed == cb.seed
            assert ca.control_left == cb.control_left
            assert ca.control_right == cb.control_right


@pytest.mark.parametrize(
    "lib_age,source_age,stale",
    [(None, 10, True), (5, 10, False), (20, 10, True)],
)
def test_native_library_rebuilds_when_missing_or_older(
    tmp_path, monkeypatch, lib_age, source_age, stale
):
    """The untracked library is rebuilt from `native/` whenever it is
    absent or older than any source file there."""
    import os
    import time

    now = time.time()
    src = tmp_path / "dpf_kernels.cc"
    src.write_text("// source")
    os.utime(src, (now - source_age, now - source_age))
    lib = tmp_path / "libdpf_native.so"
    if lib_age is not None:
        lib.write_bytes(b"\x7fELF")
        os.utime(lib, (now - lib_age, now - lib_age))
    (tmp_path / ".build.lock").write_text("")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    assert native._stale() is stale
