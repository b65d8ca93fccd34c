"""A plain reference for incremental DPF evaluation with integer values.

It walks a key from the root to each requested output, one tree level at
a time, with the `cryptography` package's AES and nothing of the
evaluation code under test: the fixed-key MMO hash
H_k(x) = AES_k(sigma(x)) ^ sigma(x), the correction words, the value
hash and the value correction, as `DistributedPointFunction` defines
them. Only the keys' data is read. Vectorized over the outputs with
numpy; meant for tests, not for speed.

    ref = PlainDpf([21, 23], value_bits=32)
    shares = ref.outputs(key, hierarchy_level=1, indices=np.array([...]))
"""

from __future__ import annotations

import hashlib
from typing import Sequence

import numpy as np
from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes


def _fixed_key(name: str) -> bytes:
    return hashlib.sha256(
        f"DistributedPointFunctionTpu::{name}".encode()
    ).digest()[:16]


KEY_LEFT = _fixed_key("kPrgKeyLeft")
KEY_RIGHT = _fixed_key("kPrgKeyRight")
KEY_VALUE = _fixed_key("kPrgKeyValue")


def _blocks(x: int, n: int) -> np.ndarray:
    """A 128-bit int as uint8[n, 16] little-endian rows."""
    return np.tile(np.frombuffer(x.to_bytes(16, "little"), dtype=np.uint8),
                   (n, 1))


def mmo(key: bytes, blocks: np.ndarray) -> np.ndarray:
    """H_key on uint8[n, 16] little-endian blocks: sigma(x) = (hi ^ lo,
    hi) as (high, low) 64-bit halves, then AES_key(sigma) ^ sigma."""
    sig = np.concatenate([blocks[:, 8:], blocks[:, 8:] ^ blocks[:, :8]],
                         axis=1)
    enc = Cipher(algorithms.AES(key), modes.ECB()).encryptor()
    out = enc.update(np.ascontiguousarray(sig).tobytes()) + enc.finalize()
    return np.frombuffer(out, dtype=np.uint8).reshape(-1, 16) ^ sig


class PlainDpf:
    """An incremental DPF over the given log domain sizes, `IntType(bits)`
    values at every level (bits in 8, 16, 32, 64)."""

    def __init__(self, log_domains: Sequence[int], value_bits: int):
        self.log_domains = list(log_domains)
        self.bits = int(value_bits)
        log_bits = max(0, (self.bits - 1).bit_length())
        self.tree_levels = []
        needed = 0
        for lds in self.log_domains:
            level = max(needed, lds - 7 + min(log_bits, 7))
            self.tree_levels.append(level)
            needed = level + 1

    def value_correction(self, key, hierarchy_level: int) -> list:
        if hierarchy_level == len(self.log_domains) - 1:
            return list(key.last_level_value_correction)
        return list(
            key.correction_words[self.tree_levels[hierarchy_level]]
            .value_correction
        )

    def outputs(self, key, hierarchy_level: int,
                indices: np.ndarray) -> np.ndarray:
        """The party's shares at `indices` of the hierarchy level's domain,
        as uint64 values mod 2^bits."""
        indices = np.asarray(indices, dtype=np.uint64)
        n = len(indices)
        depth = self.tree_levels[hierarchy_level]
        shift = np.uint64(self.log_domains[hierarchy_level] - depth)
        nodes = indices >> shift
        elements = (indices - (nodes << shift)).astype(np.int64)

        seeds = _blocks(int(key.seed), n)
        control = np.full(n, key.party & 1, dtype=np.uint8)
        for level in range(1, depth + 1):
            cw = key.correction_words[level - 1]
            right = ((nodes >> np.uint64(depth - level)) & np.uint64(1)) != 0
            h = np.where(right[:, None], mmo(KEY_RIGHT, seeds),
                         mmo(KEY_LEFT, seeds))
            h = h ^ np.where(control[:, None] != 0,
                             _blocks(int(cw.seed), n), np.uint8(0))
            cw_dir = np.where(right, int(cw.control_right),
                              int(cw.control_left)).astype(np.uint8)
            control = (h[:, 0] & 1) ^ (control & cw_dir)
            h[:, 0] &= 0xFE
            seeds = h

        per_block = 128 // self.bits
        words = np.ascontiguousarray(mmo(KEY_VALUE, seeds)).view(
            f"<u{self.bits // 8}"
        ).reshape(n, per_block).astype(np.uint64)
        value = words[np.arange(n), elements]
        vc = np.array(
            [int(v) for v in self.value_correction(key, hierarchy_level)],
            dtype=np.uint64,
        )
        mask = np.uint64((1 << self.bits) - 1) if self.bits < 64 else None
        value = np.where(control != 0, value + vc[elements], value)
        if key.party == 1:
            value = np.uint64(0) - value
        return value & mask if mask is not None else value
