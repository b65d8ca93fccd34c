"""ctypes bindings to the native C++ CPU kernels (`native/`).

The native library is the framework's CPU oracle — the role the scalar
`EvaluateSeedsNoHwy` / `InnerProductNoHwy` paths play in the reference
(`dpf/internal/evaluate_prg_hwy.cc:552-634`,
`pir/internal/inner_product_hwy.cc:270-296`). The TPU kernels are
differential-tested against it, and host-side tooling can use it without
JAX.

The shared library is built from the committed sources with
`native/build.sh` (g++) whenever it is missing or older than any file
under `native/`; the binding is plain ctypes — no pybind11 in this
environment.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from . import keys as fixed_keys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdpf_native.so")

_lib: Optional[ctypes.CDLL] = None
_keys_ctx = None
# Serializes the build+load: two threads racing get_lib() on a cold
# checkout must not CDLL a half-written .so mid-build.
_lib_lock = threading.Lock()


def _stale() -> bool:
    """True when the library is missing or older than any source."""
    try:
        built = os.path.getmtime(_LIB_PATH)
    except OSError:
        return True
    return any(
        os.path.getmtime(os.path.join(_NATIVE_DIR, name)) > built
        for name in os.listdir(_NATIVE_DIR)
        if not name.startswith(".") and name != "libdpf_native.so"
    )


def _build_if_stale() -> None:
    """Rebuild under an exclusive file lock, so concurrent processes
    (test workers on a fresh checkout) build once and the rest wait."""
    if not _stale():
        return
    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _stale():
            subprocess.run(
                ["sh", os.path.join(_NATIVE_DIR, "build.sh")],
                check=True,
                capture_output=True,
            )


def _u8(arr) -> np.ndarray:
    return np.ascontiguousarray(arr, dtype=np.uint8)


def get_lib() -> ctypes.CDLL:
    """Loads (building if needed) the native library. Thread-safe: the
    build+load is serialized so concurrent callers never load a
    half-written .so."""
    global _lib, _keys_ctx
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        return _load_locked()


def _load_locked() -> ctypes.CDLL:
    global _lib, _keys_ctx
    _build_if_stale()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.dpf_create_keys.restype = ctypes.c_void_p
    lib.dpf_create_keys.argtypes = [ctypes.c_char_p] * 3
    lib.dpf_free_keys.argtypes = [ctypes.c_void_p]
    _lib = lib
    _keys_ctx = ctypes.c_void_p(
        lib.dpf_create_keys(
            bytes(fixed_keys.PRG_KEY_LEFT),
            bytes(fixed_keys.PRG_KEY_RIGHT),
            bytes(fixed_keys.PRG_KEY_VALUE),
        )
    )
    return lib


def _ctx():
    get_lib()
    return _keys_ctx


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def mmo_hash(which: int, blocks: np.ndarray) -> np.ndarray:
    """MMO hash of uint8[n, 16] blocks; which: 0=left, 1=right, 2=value."""
    lib = get_lib()
    blocks = _u8(blocks).reshape(-1, 16)
    out = np.empty_like(blocks)
    lib.dpf_mmo_hash(
        _ctx(), ctypes.c_int(which), _ptr(blocks), _ptr(out),
        ctypes.c_int64(blocks.shape[0]),
    )
    return out


def expand_level(seeds: np.ndarray, control: np.ndarray, cw_seed: np.ndarray,
                 cw_left: int, cw_right: int):
    """One expansion level: uint8[n,16] -> uint8[2n,16] (interleaved L/R)."""
    lib = get_lib()
    seeds = _u8(seeds).reshape(-1, 16)
    n = seeds.shape[0]
    control = _u8(control)
    cw_seed = _u8(cw_seed).reshape(16)
    seeds_out = np.empty((2 * n, 16), dtype=np.uint8)
    control_out = np.empty((2 * n,), dtype=np.uint8)
    lib.dpf_expand_level(
        _ctx(), _ptr(seeds), _ptr(control), _ptr(cw_seed),
        ctypes.c_uint8(cw_left), ctypes.c_uint8(cw_right),
        _ptr(seeds_out), _ptr(control_out), ctypes.c_int64(n),
    )
    return seeds_out, control_out


def evaluate_seeds(seeds: np.ndarray, control: np.ndarray, paths: np.ndarray,
                   cw_seeds: np.ndarray, cw_left: np.ndarray,
                   cw_right: np.ndarray, per_seed_cw: bool,
                   paths_rightshift: int = 0):
    """Walk levels for a batch of seeds (in-place on copies; returns them).

    seeds/paths: uint8[n, 16]; cw_seeds: uint8[L, m, 16] with m == 1
    (shared) or m == n (per-seed); cw_left/right: uint8[L, m].
    """
    lib = get_lib()
    seeds = _u8(seeds).reshape(-1, 16).copy()
    n = seeds.shape[0]
    control = _u8(control).copy()
    paths = _u8(paths).reshape(-1, 16)
    cw_seeds = _u8(cw_seeds)
    num_levels = cw_seeds.shape[0] if cw_seeds.ndim == 3 else 0
    stride = n if per_seed_cw else 1
    cw_seeds_flat = _u8(cw_seeds).reshape(-1, 16)
    cw_left = _u8(cw_left).reshape(-1)
    cw_right = _u8(cw_right).reshape(-1)
    lib.dpf_evaluate_seeds(
        _ctx(), _ptr(seeds), _ptr(control), _ptr(paths),
        _ptr(cw_seeds_flat), _ptr(cw_left), _ptr(cw_right),
        ctypes.c_int64(n), ctypes.c_int(num_levels),
        ctypes.c_int64(stride), ctypes.c_int(paths_rightshift),
    )
    return seeds, control


def value_hash(seeds: np.ndarray, num_blocks: int) -> np.ndarray:
    """uint8[n, 16] -> uint8[n, num_blocks, 16] output PRG."""
    lib = get_lib()
    seeds = _u8(seeds).reshape(-1, 16)
    n = seeds.shape[0]
    out = np.empty((n, num_blocks, 16), dtype=np.uint8)
    lib.dpf_value_hash(
        _ctx(), _ptr(seeds), _ptr(out), ctypes.c_int64(n),
        ctypes.c_int(num_blocks),
    )
    return out


def inner_product(db_words: np.ndarray, selections: np.ndarray) -> np.ndarray:
    """XOR inner product: uint32[R, W] x uint8[nq, B, 16] -> uint32[nq, W]."""
    lib = get_lib()
    db_words = np.ascontiguousarray(db_words, dtype=np.uint32)
    num_records, record_words = db_words.shape
    selections = _u8(selections)
    nq, num_blocks = selections.shape[0], selections.shape[1]
    out = np.empty((nq, record_words), dtype=np.uint32)
    lib.dpf_inner_product(
        _ptr(db_words), ctypes.c_int64(num_records),
        ctypes.c_int64(record_words), _ptr(selections),
        ctypes.c_int64(nq), ctypes.c_int64(num_blocks), _ptr(out),
    )
    return out


def keygen_batch_dense(
    root_seeds: np.ndarray,
    alphas: np.ndarray,
    betas: np.ndarray,
    levels: int,
):
    """Native batched dense-PIR keygen (`native/keygen.cc`).

    root_seeds: uint8[2, n, 16] party-major; alphas: uint64[n];
    betas: uint8[n, 16]. Returns (cw_seeds uint8[levels, n, 16],
    cw_ctrl uint8[levels, n, 2], last_vc uint8[n, 16]) — bit-identical
    to `DistributedPointFunction.generate_keys_batch` on the same seeds.
    """
    lib = get_lib()
    if not hasattr(lib.dpf_keygen_batch_dense, "_configured"):
        lib.dpf_keygen_batch_dense.argtypes = [
            ctypes.c_char_p,  # key_left
            ctypes.c_char_p,  # key_right
            ctypes.c_char_p,  # key_value
            ctypes.c_void_p,  # root_seeds
            ctypes.c_void_p,  # alphas
            ctypes.c_void_p,  # betas
            ctypes.c_int,  # levels
            ctypes.c_int64,  # n
            ctypes.c_void_p,  # cw_seeds out
            ctypes.c_void_p,  # cw_ctrl out
            ctypes.c_void_p,  # last_vc out
        ]
        lib.dpf_keygen_batch_dense._configured = True
    n = alphas.shape[0]
    root_seeds = _u8(root_seeds)
    alphas = np.ascontiguousarray(alphas, dtype=np.uint64)
    betas = _u8(betas)
    cw_seeds = np.zeros((levels, n, 16), dtype=np.uint8)
    cw_ctrl = np.zeros((levels, n, 2), dtype=np.uint8)
    last_vc = np.zeros((n, 16), dtype=np.uint8)
    lib.dpf_keygen_batch_dense(
        bytes(fixed_keys.PRG_KEY_LEFT),
        bytes(fixed_keys.PRG_KEY_RIGHT),
        bytes(fixed_keys.PRG_KEY_VALUE),
        _ptr(root_seeds),
        _ptr(alphas),
        _ptr(betas),
        levels,
        n,
        _ptr(cw_seeds),
        _ptr(cw_ctrl),
        _ptr(last_vc),
    )
    return cw_seeds, cw_ctrl, last_vc


def cuckoo_build(
    keys: "list[bytes]",
    seeds: "list[bytes]",
    num_buckets: int,
    max_relocations: int,
    rng_seed: int = 0x5EED,
) -> np.ndarray:
    """Native cuckoo-table build (`native/cuckoo_build.cc`).

    `seeds[i]` is hash function i's full SHA256 prefix (family seed +
    derivation seed, `hashing/hash_family.py` semantics). Returns
    int64[num_buckets] of key indices (-1 = empty bucket) — a legal
    cuckoo assignment (each key lands in one of its hash buckets; layout
    may differ from the Python builder's, which the protocol permits).
    Raises on placement failure, like `CuckooHashTable.insert`.
    """
    lib = get_lib()
    if not hasattr(lib.dpf_cuckoo_build, "_configured"):
        lib.dpf_cuckoo_build.argtypes = [
            ctypes.c_void_p,  # keys_concat
            ctypes.c_void_p,  # key_offsets
            ctypes.c_int64,  # num_keys
            ctypes.c_void_p,  # seeds_concat
            ctypes.c_void_p,  # seed_offsets
            ctypes.c_int,  # num_hashes
            ctypes.c_int64,  # num_buckets
            ctypes.c_int64,  # max_relocations
            ctypes.c_uint64,  # rng_seed
            ctypes.c_void_p,  # out_slots
        ]
        lib.dpf_cuckoo_build.restype = ctypes.c_int
        lib.dpf_cuckoo_build._configured = True
    keys_concat = np.frombuffer(
        b"".join(keys), dtype=np.uint8
    ) if keys else np.zeros(0, np.uint8)
    key_offsets = np.zeros(len(keys) + 1, dtype=np.uint64)
    np.cumsum([len(k) for k in keys], out=key_offsets[1:])
    seeds_concat = np.frombuffer(b"".join(seeds), dtype=np.uint8)
    seed_offsets = np.zeros(len(seeds) + 1, dtype=np.uint64)
    np.cumsum([len(s) for s in seeds], out=seed_offsets[1:])
    out = np.empty(num_buckets, dtype=np.int64)
    rc = lib.dpf_cuckoo_build(
        _ptr(keys_concat),
        _ptr(key_offsets),
        ctypes.c_int64(len(keys)),
        _ptr(seeds_concat),
        _ptr(seed_offsets),
        ctypes.c_int(len(seeds)),
        ctypes.c_int64(num_buckets),
        ctypes.c_int64(max_relocations),
        ctypes.c_uint64(rng_seed),
        _ptr(out),
    )
    if rc == -1:
        raise RuntimeError(
            "cuckoo insertion failed: relocation budget exhausted"
        )
    if rc != 0:
        raise ValueError(f"dpf_cuckoo_build rejected arguments (rc={rc})")
    return out
