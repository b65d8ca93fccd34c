"""Plain reference for hierarchical DPF evaluation, independent of the
program under test.

Nothing here imports the program: it walks a key from the root to each
requested output, one tree level at a time, with the `cryptography`
package's AES. The fixed-key MMO hash H_k(x) = AES_k(sigma(x)) ^
sigma(x), with sigma(x) = (hi ^ lo, hi) as (high, low) 64-bit halves;
the fixed keys are the first 16 bytes of SHA-256 of their published
names. At each tree level the child seed is H_left or H_right of the
parent by the path bit, XORed with the level's correction word where
the parent's control bit is set; the child's control bit is the hash's
LSB XOR (parent bit AND the correction word's bit for that side), and
the LSB is cleared. An output is element e of H_value(seed), plus the
level's value correction e where the control bit is set, negated for
party 1, mod 2^bits. Only the keys' data is read (seed, party,
correction words, value corrections).

Tree depth of a hierarchy level: log_domain - 7 + ceil(log2(bits)),
at least one more than the level before.
"""

from __future__ import annotations

import hashlib

import numpy as np


def _fixed_key(name: str) -> bytes:
    return hashlib.sha256(
        f"DistributedPointFunctionTpu::{name}".encode()
    ).digest()[:16]


KEY_LEFT = _fixed_key("kPrgKeyLeft")
KEY_RIGHT = _fixed_key("kPrgKeyRight")
KEY_VALUE = _fixed_key("kPrgKeyValue")


def _blocks(x: int, n: int) -> np.ndarray:
    return np.tile(np.frombuffer(int(x).to_bytes(16, "little"),
                                 dtype=np.uint8), (n, 1))


def mmo(key: bytes, blocks: np.ndarray) -> np.ndarray:
    from cryptography.hazmat.primitives.ciphers import (
        Cipher,
        algorithms,
        modes,
    )

    sig = np.concatenate([blocks[:, 8:], blocks[:, 8:] ^ blocks[:, :8]],
                         axis=1)
    enc = Cipher(algorithms.AES(key), modes.ECB()).encryptor()
    out = enc.update(np.ascontiguousarray(sig).tobytes()) + enc.finalize()
    return np.frombuffer(out, dtype=np.uint8).reshape(-1, 16) ^ sig


def tree_depths(log_domains, bits: int) -> list:
    log_bits = max(0, (bits - 1).bit_length())
    depths, needed = [], 0
    for lds in log_domains:
        depth = max(needed, lds - 7 + min(log_bits, 7))
        depths.append(depth)
        needed = depth + 1
    return depths


def shares(key, log_domains, bits: int, level: int,
           indices: np.ndarray) -> np.ndarray:
    """The key's shares at `indices` (uint64) of hierarchy level
    `level`'s domain, as uint64 values mod 2^bits."""
    depths = tree_depths(log_domains, bits)
    indices = np.asarray(indices, dtype=np.uint64)
    n = len(indices)
    depth = depths[level]
    shift = np.uint64(log_domains[level] - depth)
    nodes = indices >> shift
    elements = (indices - (nodes << shift)).astype(np.int64)

    seeds = _blocks(key.seed, n)
    control = np.full(n, key.party & 1, dtype=np.uint8)
    for t in range(1, depth + 1):
        cw = key.correction_words[t - 1]
        right = ((nodes >> np.uint64(depth - t)) & np.uint64(1)) != 0
        h = np.where(right[:, None], mmo(KEY_RIGHT, seeds),
                     mmo(KEY_LEFT, seeds))
        h = h ^ np.where(control[:, None] != 0, _blocks(cw.seed, n),
                         np.uint8(0))
        side = np.where(right, int(cw.control_right),
                        int(cw.control_left)).astype(np.uint8)
        control = (h[:, 0] & 1) ^ (control & side)
        h[:, 0] &= 0xFE
        seeds = h

    words = np.ascontiguousarray(mmo(KEY_VALUE, seeds)).view(
        f"<u{bits // 8}"
    ).reshape(n, 128 // bits).astype(np.uint64)
    value = words[np.arange(n), elements]
    corrections = (key.last_level_value_correction
                   if level == len(log_domains) - 1
                   else key.correction_words[depth].value_correction)
    vc = np.array([int(v) for v in corrections], dtype=np.uint64)
    value = np.where(control != 0, value + vc[elements], value)
    if key.party == 1:
        value = np.uint64(0) - value
    return value & np.uint64((1 << bits) - 1)
