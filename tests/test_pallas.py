"""Pallas kernel differential tests (interpret mode on CPU).

Mirrors the reference's per-target kernel testing discipline
(`pir/internal/inner_product_hwy_test.cc:427-434`): the Pallas kernel must
be bit-identical to the jnp implementation and the numpy oracle.
"""

import numpy as np
import pytest

from distributed_point_functions_tpu.ops.inner_product import (
    pack_selection_bits_np,
    xor_inner_product,
    xor_inner_product_np,
)
from distributed_point_functions_tpu.ops.inner_product_pallas import (
    permute_db_bitmajor,
    xor_inner_product_pallas,
    xor_inner_product_pallas_staged,
)

RNG = np.random.default_rng(17)


@pytest.mark.parametrize(
    "num_records,num_words,nq",
    [(256, 8, 1), (1024, 64, 4), (384, 5, 2), (8192, 16, 16)],
)
def test_pallas_inner_product_matches_oracles(num_records, num_words, nq):
    db = RNG.integers(0, 1 << 32, (num_records, num_words), dtype=np.uint32)
    bits = RNG.integers(0, 2, (nq, num_records), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    got = np.asarray(xor_inner_product_pallas(db, sel, interpret=True))
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))
    np.testing.assert_array_equal(
        got, np.asarray(xor_inner_product(db, sel))
    )


def test_pallas_inner_product_staged_bitmajor():
    # The serving path stages the bit-major permutation once; staged and
    # per-call entries must agree with the oracle.
    db = RNG.integers(0, 1 << 32, (1152, 4), dtype=np.uint32)
    bits = RNG.integers(0, 2, (3, 1152), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    db_perm = np.asarray(permute_db_bitmajor(db))
    # 1152 records pad to 4096 = 128 groups of 32 (full-lane tiles).
    assert db_perm.shape == (32, 128, 4)
    # Spot-check the permutation: record 32g+b lands at [b, g].
    np.testing.assert_array_equal(db_perm[5, 7], db[32 * 7 + 5])
    assert not db_perm[:, 36:].any()  # zero padding
    got = np.asarray(
        xor_inner_product_pallas_staged(db_perm, sel, interpret=True)
    )
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))


@pytest.mark.parametrize("nq", [1, 3, 65, 100])
def test_pallas_inner_product_odd_query_counts(nq):
    # Regression: query counts with no multiple-of-8 divisor used to drive
    # the tile search to zero (ZeroDivisionError). Queries are now padded.
    db = RNG.integers(0, 1 << 32, (256, 4), dtype=np.uint32)
    bits = RNG.integers(0, 2, (nq, 256), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    got = np.asarray(xor_inner_product_pallas(db, sel, interpret=True))
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))


def test_bitplane_jnp_matches_xor_paths():
    """The pure-jnp MXU bit-plane inner product (the serving path's
    middle fallback) must match the mask-and-XOR path bit for bit."""
    import numpy as np
    import jax.numpy as jnp

    from distributed_point_functions_tpu.ops.inner_product import (
        xor_inner_product,
        xor_inner_product_bitplane,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
    )

    rng = np.random.default_rng(31)
    for R, W, nq in [(512, 8, 5), (4096, 20, 3)]:
        db = jnp.asarray(rng.integers(0, 1 << 32, (R, W), dtype=np.uint32))
        sel = jnp.asarray(
            rng.integers(0, 1 << 32, (nq, R // 128, 4), dtype=np.uint32)
        )
        a = np.asarray(
            xor_inner_product_bitplane(permute_db_bitmajor(db), sel)
        )
        b = np.asarray(xor_inner_product(db, sel))
        np.testing.assert_array_equal(a, b)


def test_database_serves_via_bitplane(monkeypatch):
    """DPF_TPU_INNER_PRODUCT=bitplane routes the database through the
    bit-plane path with identical record bytes."""
    import numpy as np

    from distributed_point_functions_tpu.ops.inner_product import (
        pack_selection_bits_np,
    )
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )

    rng = np.random.default_rng(32)
    records = [rng.bytes(24) for _ in range(300)]
    db = DenseDpfPirDatabase(records)
    bits = rng.integers(0, 2, (4, db.num_selection_bits), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)

    monkeypatch.setenv("DPF_TPU_INNER_PRODUCT", "jnp")
    a = db.inner_product_with(sel)
    monkeypatch.setenv("DPF_TPU_INNER_PRODUCT", "bitplane")
    b = db.inner_product_with(sel)
    assert a == b


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize(
    "num_records,num_words,nq",
    [(256, 8, 1), (1024, 64, 4), (384, 5, 2), (8192, 16, 16)],
)
def test_pallas_v2_matches_oracles(num_records, num_words, nq, int8):
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        xor_inner_product_pallas2_staged,
    )

    db = RNG.integers(0, 1 << 32, (num_records, num_words), dtype=np.uint32)
    bits = RNG.integers(0, 2, (nq, num_records), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    got = np.asarray(
        xor_inner_product_pallas2_staged(
            permute_db_bitmajor(db), sel, int8=int8, interpret=True
        )
    )
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))


@pytest.mark.parametrize("tile_groups,j_chunk", [(8, 4), (16, 16), (64, 32)])
def test_pallas_v2_tile_variants(tile_groups, j_chunk):
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        xor_inner_product_pallas2_staged,
    )

    # W=16: wide enough that the narrow-record cap leaves j_chunk alone,
    # so each declared chunk size actually runs in the kernel.
    db = RNG.integers(0, 1 << 32, (4096, 16), dtype=np.uint32)
    bits = RNG.integers(0, 2, (5, 4096), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    got = np.asarray(
        xor_inner_product_pallas2_staged(
            permute_db_bitmajor(db),
            sel,
            tile_groups=tile_groups,
            j_chunk=j_chunk,
            interpret=True,
        )
    )
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))


def test_pallas_v2_narrow_records_cap_j_chunk(monkeypatch):
    """W<16 must drop j_chunk to 1 (no in-kernel db repeat at all):
    Mosaic's pltpu.repeat miscompiles for sub-half-lane-tile sources —
    factors >8 mapped 2026-07-31, and the W=8 x factor-8 kernel smoke
    crash showed the true boundary is the source width. The public entry
    degrades instead of crashing; the drop must actually reach the
    jitted core, and results stay exact."""
    from distributed_point_functions_tpu.ops import inner_product_pallas as ipp

    forwarded = {}
    real_core = ipp._ip_pallas_staged_v2

    def spy(db_perm, packed, **kw):
        forwarded["j_chunk"] = kw["j_chunk"]
        return real_core(db_perm, packed, **kw)

    monkeypatch.setattr(ipp, "_ip_pallas_staged_v2", spy)
    for num_words, want_chunk in ((4, 1), (8, 1), (16, 32)):
        db = RNG.integers(0, 1 << 32, (4096, num_words), dtype=np.uint32)
        bits = RNG.integers(0, 2, (5, 4096), dtype=np.uint32)
        sel = pack_selection_bits_np(bits)
        got = np.asarray(
            ipp.xor_inner_product_pallas2_staged(
                permute_db_bitmajor(db), sel, j_chunk=32, interpret=True
            )
        )
        assert forwarded["j_chunk"] == want_chunk
        np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))


def test_pallas_v2_rejects_tiny_group_count():
    """Compiled mode refuses hand-built layouts under 16 groups (the
    selections repeat would hit the same Mosaic miscompile, factor 32)."""
    import jax.numpy as jnp

    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        xor_inner_product_pallas2_staged,
    )

    db_perm = jnp.zeros((32, 8, 4), dtype=jnp.uint32)
    sel = pack_selection_bits_np(
        RNG.integers(0, 2, (2, 256), dtype=np.uint32)
    )
    with pytest.raises(ValueError, match="16 selection groups"):
        xor_inner_product_pallas2_staged(db_perm, sel)
    # interpret mode has no Mosaic and still serves tiny layouts
    out = xor_inner_product_pallas2_staged(db_perm, sel, interpret=True)
    assert out.shape == (2, 4)


def test_database_auto_tier_raises_instead_of_falling_back(monkeypatch):
    """Auto mode on TPU serves pallas2 alone: a failing kernel raises
    and no slower tier answers in its place; forced tiers still serve
    and an unknown forced tier raises."""
    import warnings

    import jax

    from distributed_point_functions_tpu.pir import database as db_mod
    from distributed_point_functions_tpu.pir.database import (
        DenseDpfPirDatabase,
    )

    rng = np.random.default_rng(9)
    records = [rng.bytes(16) for _ in range(200)]
    db = DenseDpfPirDatabase(records)
    bits = rng.integers(0, 2, (2, db.num_selection_bits), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)

    monkeypatch.setenv("DPF_TPU_INNER_PRODUCT", "jnp")
    want = db.inner_product_with(sel)
    monkeypatch.setenv("DPF_TPU_INNER_PRODUCT", "bitplane")
    assert db.inner_product_with(sel) == want

    monkeypatch.setenv("DPF_TPU_INNER_PRODUCT", "nope")
    with pytest.raises(ValueError, match="unknown"):
        db.inner_product_with(sel)

    monkeypatch.setenv("DPF_TPU_INNER_PRODUCT", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert db_mod.inner_product_tier() == "pallas2"

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(db_mod, "xor_inner_product_pallas2_staged", boom)
    monkeypatch.setattr(db_mod, "xor_inner_product", boom)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(RuntimeError, match="mosaic says no"):
            DenseDpfPirDatabase(records).inner_product_with(sel)


def test_pallas_v2_wide_records_cap_query_tile():
    """W=64-word (256 B) records at a 256-query batch: the VMEM cap
    drops the query tile below the 256 default and the kernel still
    matches the oracle (grid covers all query tiles)."""
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        xor_inner_product_pallas2_staged,
    )

    db = RNG.integers(0, 1 << 32, (4096, 64), dtype=np.uint32)
    bits = RNG.integers(0, 2, (256, 4096), dtype=np.uint32)
    sel = pack_selection_bits_np(bits)
    got = np.asarray(
        xor_inner_product_pallas2_staged(
            permute_db_bitmajor(db), sel, interpret=True
        )
    )
    np.testing.assert_array_equal(got, xor_inner_product_np(db, sel))


@pytest.mark.parametrize(
    "num_groups,max_tile",
    [
        (128, 128),    # bench.py's small verify instance (4096 records)
        (32768, 128),  # headline 2^20 records
        (131072, 128), # dense_big 2^22 records
        (128, 32),     # the round-2 hardware failure: requested tile 32
        (64, 128),     # small database, tile spans the axis
    ],
)
def test_group_tile_mosaic_legal(num_groups, max_tile):
    """Non-interpret lowering must pick selection-block lane dims Mosaic
    accepts: divisible by 128 or equal to the whole group axis. The
    round-2 TPU window showed tile_groups=32 on a [8, 128] selections
    array is rejected by Mosaic ('block shape ... divisible by 8 and 128
    respectively'), silently dropping the v2 MXU kernel from the tier
    chain."""
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        _pick_group_tile,
    )

    tg = _pick_group_tile(num_groups, max_tile=max_tile, lane_step=128)
    assert num_groups % tg == 0
    assert tg % 128 == 0 or tg == num_groups
