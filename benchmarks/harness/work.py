"""Work counts from shapes: what an operation has to move or compute,
independent of which implementation or tier runs it.

XOR inner product (dense PIR, one batch): every key selects records by
one bit each, and the answer is the XOR of the selected records. The
least a device can move is the whole table once per batch, the selection
bits, and the answers. Records are padded to 32-bit words and the table
to a multiple of 128 records, as the database stores them.

Full-domain DPF evaluation (one key, log domain `ld`, `value_bits`-bit
values): a GGM tree whose leaves each hold one 128-bit block of
`128 // value_bits` values. Every inner node costs two fixed-key AES
hashes (left and right child), every leaf one more (the value hash),
and the output is 2^ld values.
"""

from __future__ import annotations

import math


def padded_records(num_records: int) -> int:
    return max(128, -(-num_records // 128) * 128)


def record_words_bytes(record_bytes: int) -> int:
    return max(4, -(-record_bytes // 4) * 4)


def inner_product_bytes(num_keys: int, num_records: int,
                        record_bytes: int) -> int:
    """Least bytes one batch of `num_keys` has to move through HBM."""
    rows = padded_records(num_records)
    width = record_words_bytes(record_bytes)
    table = rows * width
    selection = num_keys * rows // 8
    answers = num_keys * width
    return table + selection + answers


def full_domain_work(log_domain: int, value_bits: int) -> dict:
    """AES hashes, leaves and output bytes of one full-domain evaluation."""
    per_block = max(1, 128 // value_bits)
    tree_levels = max(0, log_domain - int(math.log2(per_block)))
    inner_nodes = (1 << tree_levels) - 1
    leaf_blocks = 1 << tree_levels
    return {
        "leaves": 1 << log_domain,
        "aes_hashes": 2 * inner_nodes + leaf_blocks,
        "output_bytes": (1 << log_domain) * value_bits // 8,
    }
