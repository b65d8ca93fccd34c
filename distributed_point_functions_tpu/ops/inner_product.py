"""Packed-XOR inner product between a dense database and selection bits.

The TPU redesign of the reference's Highway kernel
(`pir/internal/inner_product_hwy.h:38`, `.cc:300-334`): for each query's
packed selection-bit vector, XOR-accumulate every database record whose bit
is 1.

Layout (all little-endian):

* database: `uint32[num_records_padded, record_words]` — every record
  zero-padded to the maximum record size, record count padded to a multiple
  of 128 (one selection block covers 128 records, matching the reference's
  `kBitsPerBlock`, `inner_product_hwy.cc:41`).
* selections: `uint32[num_queries, num_blocks, 4]` — 128 selection bits per
  block; the bit for record `r` is bit `r % 32` of limb `(r % 128) // 32` of
  block `r // 128` (the `XorWrapper<uint128>` bit order of
  `dense_dpf_pir_client.cc:92-103`).

The kernel is bandwidth-bound: one pass over the database serves the entire
query batch, with the per-query accumulators living in registers/VMEM. A
`lax.scan` over record chunks keeps the masked intermediate at
`[num_queries, chunk, record_words]` so XLA can pipeline HBM reads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

U32 = jnp.uint32


def unpack_selection_bits(selections: jnp.ndarray) -> jnp.ndarray:
    """uint32[..., B, 4] packed blocks -> uint32[..., B*128] per-record bits."""
    shifts = jnp.arange(32, dtype=U32)
    bits = (selections[..., None] >> shifts) & U32(1)  # [..., B, 4, 32]
    return bits.reshape(selections.shape[:-2] + (selections.shape[-2] * 128,))


def pack_selection_bits_np(bits: np.ndarray) -> np.ndarray:
    """bool/uint [..., n] -> uint32[..., ceil(n/128), 4] packed blocks."""
    n = bits.shape[-1]
    nb = (n + 127) // 128
    padded = np.zeros(bits.shape[:-1] + (nb * 128,), dtype=np.uint32)
    padded[..., :n] = bits.astype(np.uint32) & 1
    lanes = padded.reshape(bits.shape[:-1] + (nb, 4, 32))
    out = np.zeros(bits.shape[:-1] + (nb, 4), dtype=np.uint32)
    for k in range(32):
        out |= lanes[..., k] << np.uint32(k)
    return out


def varying_zeros(shape, *like) -> jnp.ndarray:
    """uint32 zeros that vary over the same shard_map axes as `like`.

    A scan carry started from plain zeros is unvarying; inside shard_map
    the body's XOR with sharded operands makes it varying, and the carry
    types must match. Outside shard_map this is just `jnp.zeros`."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    zeros = jnp.zeros(shape, dtype=U32)
    return lax.pcast(zeros, tuple(vma), to="varying") if vma else zeros


@functools.partial(jax.jit, static_argnames=("chunk",))
def xor_inner_product(
    db_words: jnp.ndarray, selections: jnp.ndarray, chunk: int = 256
) -> jnp.ndarray:
    """XOR inner product.

    db_words: uint32[R, W] with R a multiple of 128 (zero rows beyond the
    real record count); selections: uint32[nq, B, 4] with B*128 >= R (extra
    selection bits beyond R are ignored). Returns uint32[nq, W].
    """
    num_records, num_words = db_words.shape
    if num_records % 128 != 0:
        raise ValueError("record count must be padded to a multiple of 128")
    bits = unpack_selection_bits(selections)[:, :num_records]  # [nq, R]
    if chunk > num_records:
        chunk = num_records
    num_chunks = num_records // chunk
    rem = num_records - num_chunks * chunk

    def xor_chunk(bits_c, db_c):
        # bits_c: [nq, C]; db_c: [C, W]. Mask rows and XOR-reduce over C.
        mask = (U32(0) - bits_c)[:, :, None]  # 0 or 0xFFFFFFFF
        masked = mask & db_c[None, :, :]
        return lax.reduce(
            masked, U32(0), lambda a, b: lax.bitwise_xor(a, b), (1,)
        )

    acc = varying_zeros((selections.shape[0], num_words), bits, db_words)
    if num_chunks > 0:
        db_main = db_words[: num_chunks * chunk].reshape(
            num_chunks, chunk, num_words
        )
        bits_main = (
            bits[:, : num_chunks * chunk]
            .reshape(bits.shape[0], num_chunks, chunk)
            .transpose(1, 0, 2)
        )

        def body(acc, x):
            bits_c, db_c = x
            return acc ^ xor_chunk(bits_c, db_c), None

        acc, _ = lax.scan(body, acc, (bits_main, db_main))
    if rem:
        acc = acc ^ xor_chunk(bits[:, -rem:], db_words[-rem:])
    return acc


def xor_inner_product_accumulate(
    acc: jnp.ndarray,
    db_span: jnp.ndarray,
    selections: jnp.ndarray,
    chunk: int = 256,
) -> jnp.ndarray:
    """Partial-accumulate entry for the streaming serving scan: XOR the
    inner product of one database block span into per-query accumulators.

    acc: uint32[nq, W] running XOR accumulators; db_span: uint32[R, W]
    one span of (permuted) record rows, R a multiple of 128; selections:
    uint32[nq, B, 4] the selection blocks covering exactly that span.
    Returns the updated uint32[nq, W] accumulators; XOR-accumulating
    every span of a partition of the database equals one full
    `xor_inner_product`.
    """
    return acc ^ xor_inner_product(db_span, selections, chunk=chunk)


@jax.jit
def xor_inner_product_bitplane(
    db_perm: jnp.ndarray, selections: jnp.ndarray
) -> jnp.ndarray:
    """XOR inner product as MXU bit-plane matmuls, in pure jnp.

    Same math as the Pallas kernel (`inner_product_pallas.py`): output bit
    j of word w is the parity of an integer matmul count, computed as 32
    value-bit-plane dots with exact f32 accumulation — but expressed as
    plain XLA ops so it runs on the MXU with no Mosaic dependency (the
    serving path's middle fallback between the Pallas kernel and the
    mask-and-XOR path).

    db_perm: uint32[32, G, W] bit-major staged database
    (`inner_product_pallas.permute_db_bitmajor`; R = 32G records <= 2^24
    for exact f32 counts); selections: uint32[nq, B, 4] packed blocks.
    Returns uint32[nq, W].
    """
    from .inner_product_pallas import MAX_RECORDS_EXACT

    _, num_groups, num_words = db_perm.shape
    if 32 * num_groups > MAX_RECORDS_EXACT:
        raise ValueError(
            f"bit-plane inner product supports at most "
            f"{MAX_RECORDS_EXACT} records (f32-exact parity counts); "
            f"got {32 * num_groups}"
        )
    nq = selections.shape[0]
    packed = selections.reshape(nq, -1)
    if packed.shape[1] > num_groups:
        packed = packed[:, :num_groups]
    elif packed.shape[1] < num_groups:
        packed = jnp.pad(packed, ((0, 0), (0, num_groups - packed.shape[1])))

    shifts = jnp.arange(32, dtype=U32)
    # Selection bits: [nq, 32(b), G] -> [nq, 32G] matching db_perm's
    # (b, g) flattening; 0/1 bf16 feeds the MXU.
    sel_bits = ((packed[:, None, :] >> shifts[None, :, None]) & U32(1))
    sel_f = sel_bits.reshape(nq, -1).astype(jnp.bfloat16)
    db_flat = db_perm.reshape(-1, num_words)  # [32G, W]

    def body(j, counts):
        bits_j = ((db_flat >> j.astype(U32)) & U32(1)).astype(jnp.bfloat16)
        c = jax.lax.dot_general(
            sel_f, bits_j, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [nq, W] counts for value bit j
        parity = c.astype(jnp.int32).astype(U32) & U32(1)
        return counts | (parity << j.astype(U32))

    return lax.fori_loop(
        0, 32, body, jnp.zeros((nq, num_words), dtype=U32)
    )


def xor_inner_product_np(
    db_words: np.ndarray, selections: np.ndarray
) -> np.ndarray:
    """Numpy oracle with the scalar reference semantics
    (`inner_product_hwy.cc:270-296`)."""
    num_records, num_words = db_words.shape
    nq = selections.shape[0]
    out = np.zeros((nq, num_words), dtype=np.uint32)
    for q in range(nq):
        for r in range(num_records):
            block = r // 128
            limb_idx = (r % 128) // 32
            bit = (selections[q, block, limb_idx] >> (r % 32)) & 1
            if bit:
                out[q] ^= db_words[r]
    return out
