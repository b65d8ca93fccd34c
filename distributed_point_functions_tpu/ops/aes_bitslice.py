"""Fully-bitsliced AES-128 for TPU: 32 blocks per uint32 word.

The first-generation device kernel (`aes.py:aes_encrypt`) evaluates the
S-box GF(2^8) circuit on bit-*planes of one block each* — every uint32 lane
carries a single live bit, so the VPU runs at 1/32 utilization through the
~500-gate inversion circuit. This module packs the same circuit densely:

* state = `uint32[16 bytes, 8 bits, G]` where word `g` of plane (j, i)
  holds bit i of byte j for blocks 32g..32g+31 (one bit per block).
* A 32x32 bit-matrix transpose (Hacker's Delight `transpose32`, vectorized
  over groups with 5 masked swap rounds) converts between the framework's
  `uint32[N, 4]` little-endian limb blocks and plane layout.
* SubBytes runs the existing x^254 inversion circuit once, vectorized over
  the 16-byte axis, at full word occupancy — ~32x less VPU work per block.
* ShiftRows is a static reindex of the byte axis; MixColumns is plane
  rewiring + XORs (xtime = shift bit planes up with poly-tap feedback);
  AddRoundKey XORs all-ones constants (or select-mask words for the
  per-block two-key variant, mirroring the reference's per-lane key mask,
  `dpf/internal/aes_128_fixed_key_hash_hwy.h:123-155`).

Semantics are identical to `aes.aes_encrypt` / `aes.aes_encrypt_select`;
`tests/test_aes.py` differential-tests both against the FIPS-197 numpy
oracle.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import aes as _aes
from . import aes_sbox_tower as _tower

U32 = jnp.uint32

# Byte-axis permutation for ShiftRows (byte j holds state column j//4, row
# j%4 in AES order; same table as the byte-lane kernel).
_SHIFT_ROWS = _aes._SHIFT_ROWS

# MixColumns left-rotates bit planes through xtime; poly taps for x*b:
# out[0]=in[7], out[1]=in[0]^in[7], out[3]=in[2]^in[7], out[4]=in[3]^in[7].
_XTIME_TAPS = (1, 3, 4)


def _transpose32(x: jnp.ndarray) -> jnp.ndarray:
    """32x32 bit-matrix transpose over the last axis ([..., 32] uint32).

    Result `t` satisfies `(t[..., b] >> i) & 1 == (x[..., i] >> b) & 1`.
    """
    masks = (
        U32(0x0000FFFF),
        U32(0x00FF00FF),
        U32(0x0F0F0F0F),
        U32(0x33333333),
        U32(0x55555555),
    )
    j = 16
    for m in masks:
        r = x.reshape(x.shape[:-1] + (32 // (2 * j), 2, j))
        a = r[..., 0, :]
        b = r[..., 1, :]
        t = (a ^ (b << j)) & ~m
        a2 = a ^ t
        b2 = b ^ (t >> j)
        x = jnp.stack([a2, b2], axis=-2).reshape(x.shape)
        j >>= 1
    return x


def limbs_to_planes(blocks: jnp.ndarray) -> jnp.ndarray:
    """uint32[G*32, 4] limb blocks -> uint32[16, 8, G] bit planes."""
    n = blocks.shape[0]
    g = n // 32
    x = blocks.reshape(g, 32, 4)
    planes = []
    for l in range(4):
        t = _transpose32(x[:, :, l])  # [g, 32]
        planes.append(jnp.moveaxis(t, -1, 0))  # [32, g]: bit b of limb l
    stacked = jnp.stack(planes, axis=0)  # [4, 32, g]
    # limb l bit b -> byte 4l + b//8, bit b%8
    return stacked.reshape(4, 4, 8, -1).reshape(16, 8, -1)


def planes_to_limbs(planes: jnp.ndarray) -> jnp.ndarray:
    """uint32[16, 8, G] bit planes -> uint32[G*32, 4] limb blocks."""
    g = planes.shape[-1]
    stacked = planes.reshape(4, 32, g)  # [limb, bit-within-limb, group]
    limbs = []
    for l in range(4):
        t = _transpose32(jnp.moveaxis(stacked[l], 0, -1))  # [g, 32]
        limbs.append(t)  # word i = block i's limb l
    out = jnp.stack(limbs, axis=-1)  # [g, 32, 4]
    return out.reshape(g * 32, 4)


def _sub_bytes_planes(state: jnp.ndarray) -> jnp.ndarray:
    """S-box on [16, 8, G] planes via the tower-field circuit
    (`aes_sbox_tower.py`, ~4x fewer ops than the x^254 chain), vectorized
    over the byte axis."""
    planes = [state[:, i] for i in range(8)]
    out = _tower.sbox_planes_tower(planes, U32(0xFFFFFFFF))
    return jnp.stack(out, axis=1)


def _mix_columns_planes(state: jnp.ndarray) -> jnp.ndarray:
    """MixColumns on [16, 8, G]: per column, o_r = s_r ^ t ^ xtime(s_r^s_{r+1})."""
    s = state.reshape(4, 4, 8, -1)  # [column, row, bit, G]
    t = s[:, 0] ^ s[:, 1] ^ s[:, 2] ^ s[:, 3]  # [column, bit, G]
    outs = []
    for r in range(4):
        u = s[:, r] ^ s[:, (r + 1) % 4]  # operand of xtime
        # xtime on planes: shift bits up, feed bit 7 into taps {0,1,3,4}.
        hi = u[:, 7]
        xt = [hi]
        for b in range(1, 8):
            v = u[:, b - 1]
            if b in _XTIME_TAPS:
                v = v ^ hi
            xt.append(v)
        outs.append(s[:, r] ^ t ^ jnp.stack(xt, axis=1))
    return jnp.stack(outs, axis=1).reshape(16, 8, -1)


def _rk_bits(round_keys: np.ndarray) -> np.ndarray:
    """uint8[11, 16] schedule -> uint8[11, 16, 8] bits."""
    rk = np.asarray(round_keys, dtype=np.uint8)
    return (rk[..., None] >> np.arange(8)) & 1


def aes_rounds_planes(
    round_keys: np.ndarray, state: jnp.ndarray
) -> jnp.ndarray:
    """The AES-128 round function on plane-layout state [16, 8, G]
    (fixed key): the transpose-free core shared by `aes_encrypt_bs` and
    the plane-resident DPF expansion (`pir/dense_eval_planes.py`)."""
    bits = _rk_bits(round_keys)
    ones = jnp.full(state.shape[-1:], 0xFFFFFFFF, dtype=U32)

    def ark(st, rnd):
        mask = jnp.asarray(bits[rnd].astype(np.uint32))[:, :, None] * ones
        return st ^ mask

    state = ark(state, 0)
    for rnd in range(1, 10):
        state = _sub_bytes_planes(state)
        state = state[_SHIFT_ROWS]
        state = _mix_columns_planes(state)
        state = ark(state, rnd)
    state = _sub_bytes_planes(state)
    state = state[_SHIFT_ROWS]
    return ark(state, 10)


def sigma_planes(state: jnp.ndarray) -> jnp.ndarray:
    """sigma(x) = (hi ^ lo, hi) on plane layout: bytes 0-7 are limbs 0-1
    (lo), bytes 8-15 limbs 2-3 (hi) — pure byte-axis rewiring + XOR
    (`aes.sigma` semantics, `dpf/aes_128_fixed_key_hash.h:28-39`)."""
    lo = state[:8]
    hi = state[8:]
    return jnp.concatenate([hi, hi ^ lo], axis=0)


def mmo_hash_planes(
    round_keys: np.ndarray, state: jnp.ndarray
) -> jnp.ndarray:
    """H(x) = AES_k(sigma(x)) ^ sigma(x), entirely in plane layout."""
    s = sigma_planes(state)
    return aes_rounds_planes(round_keys, s) ^ s


def aes_encrypt_bs(round_keys: np.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """Bitsliced AES-128 ECB on uint32[..., 4] limb blocks (fixed key)."""
    shape = blocks.shape
    flat = blocks.reshape(-1, 4)
    n = flat.shape[0]
    pad = (-n) % 32
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    state = aes_rounds_planes(round_keys, limbs_to_planes(flat))
    out = planes_to_limbs(state)
    if pad:
        out = out[:n]
    return out.reshape(shape)


def aes_rounds_select_planes(
    round_keys0: np.ndarray,
    round_keys1: np.ndarray,
    sel: jnp.ndarray,
    state: jnp.ndarray,
) -> jnp.ndarray:
    """AES-128 rounds on plane state [16, 8, G] with per-block key choice
    from a *packed* select mask (uint32[G]: bit i of word g selects the
    key of block 32g+i; 0 -> rk0, 1 -> rk1). One AES pass; each round-key
    bit-plane is composed from the mask, so path-dependent hashing costs
    no extra AES work. Shared by `aes_encrypt_select_bs` and the
    plane-resident path walks."""
    bits0 = _rk_bits(round_keys0).astype(bool)
    bits1 = _rk_bits(round_keys1).astype(bool)
    nsel = ~sel
    zeros = jnp.zeros_like(sel)
    ones = ~zeros

    def ark(st, rnd):
        # key bit = b0 & ~sel | b1 & sel, per (byte, bit) plane.
        rows = []
        for j in range(16):
            row = []
            for i in range(8):
                b0, b1 = bits0[rnd, j, i], bits1[rnd, j, i]
                if b0 and b1:
                    row.append(ones)
                elif b0:
                    row.append(nsel)
                elif b1:
                    row.append(sel)
                else:
                    row.append(zeros)
            rows.append(jnp.stack(row))
        return st ^ jnp.stack(rows)

    state = ark(state, 0)
    for rnd in range(1, 10):
        state = _sub_bytes_planes(state)
        state = state[_SHIFT_ROWS]
        state = _mix_columns_planes(state)
        state = ark(state, rnd)
    state = _sub_bytes_planes(state)
    state = state[_SHIFT_ROWS]
    return ark(state, 10)


def broadcast_cw_planes(cw: jnp.ndarray) -> jnp.ndarray:
    """uint32[4] 128-bit correction word -> all-ones/all-zeros plane masks
    [16, 8, 1] (broadcast over groups). Encodes the limbs_to_planes layout
    invariant (limb l bit b -> byte 4l + b//8, bit b%8, i.e. flat index
    32l + b) in one place."""
    shifts = jnp.arange(32, dtype=U32)
    bits = ((cw[:, None] >> shifts) & U32(1)).reshape(128)
    return (U32(0) - bits).reshape(16, 8, 1)


def pack_select_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32[n] 0/1 (n % 32 == 0) -> packed uint32[n/32] (word g bit i =
    bit of lane 32g+i)."""
    shifts = jnp.arange(32, dtype=U32)
    return ((bits.reshape(-1, 32) & U32(1)) << shifts).sum(
        axis=-1, dtype=U32
    )  # disjoint bits: sum == OR


def aes_encrypt_select_bs(
    round_keys0: np.ndarray,
    round_keys1: np.ndarray,
    select: jnp.ndarray,
    blocks: jnp.ndarray,
) -> jnp.ndarray:
    """Bitsliced AES-128 with per-block key choice (0 -> rk0, 1 -> rk1)
    on uint32[..., 4] limb blocks."""
    shape = blocks.shape
    flat = blocks.reshape(-1, 4)
    n = flat.shape[0]
    pad = (-n) % 32
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    sel_flat = jnp.broadcast_to(select, shape[:-1]).reshape(-1).astype(U32)
    if pad:
        sel_flat = jnp.pad(sel_flat, (0, pad))
    sel = pack_select_bits(sel_flat)
    state = aes_rounds_select_planes(
        round_keys0, round_keys1, sel, limbs_to_planes(flat)
    )
    out = planes_to_limbs(state)
    if pad:
        out = out[:n]
    return out.reshape(shape)


def _transpose32_rows(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """`_transpose32` over `axis` (size 32) in place of the last axis:
    row `b` of the result holds bit `b` of every row of `x`, so the
    butterflies combine whole rows and the minor axes stay lane-dense."""
    x = jnp.moveaxis(x, axis, 0)
    shape = x.shape
    masks = (0x0000FFFF, 0x00FF00FF, 0x0F0F0F0F, 0x33333333, 0x55555555)
    j = 16
    for m in masks:
        r = x.reshape((32 // (2 * j), 2, j) + shape[1:])
        a = r[:, 0]
        b = r[:, 1]
        t = (a ^ (b << j)) & U32(~m & 0xFFFFFFFF)
        x = jnp.stack([a ^ t, b ^ (t >> j)], axis=1).reshape(shape)
        j >>= 1
    return jnp.moveaxis(x, 0, axis)


def planes_to_limb_rows(planes: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """uint32[16, 8, G] bit planes -> uint32[4, G // lanes, 32, lanes]
    limbs with the blocks on lanes: out[l, r, i, c] is limb l of block
    32 * (r * lanes + c) + i. The same bits as `planes_to_limbs`, laid
    out limb-major for a consumer that keeps the blocks lane-dense;
    the plane tiles are only regrouped, never split."""
    g = planes.shape[-1]
    x = planes.reshape(4, 32, g // lanes, lanes).transpose(0, 2, 1, 3)
    return _transpose32_rows(x, 2)
