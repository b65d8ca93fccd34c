"""Work counts from shapes (work.py)."""

import work


def test_inner_product_bytes_at_the_served_size():
    table = (1 << 20) * 256
    selection = 128 * (1 << 20) // 8
    answers = 128 * 256
    assert work.inner_product_bytes(128, 1 << 20, 256) == (
        table + selection + answers
    )


def test_inner_product_bytes_pads_records_and_rows():
    # 1000 records pad to 1024 rows; 250-byte records to 252-byte rows.
    assert work.inner_product_bytes(1, 1000, 250) == 1024 * 252 + 128 + 252


def test_full_domain_work_ld20_u64():
    got = work.full_domain_work(20, 64)
    assert got["leaves"] == 1 << 20
    # two u64 values per 128-bit leaf block: a 19-level tree.
    assert got["aes_hashes"] == 2 * ((1 << 19) - 1) + (1 << 19)
    assert got["output_bytes"] == 8 << 20


def test_full_domain_work_u128_has_one_value_per_block():
    got = work.full_domain_work(10, 128)
    assert got["aes_hashes"] == 2 * ((1 << 10) - 1) + (1 << 10)
