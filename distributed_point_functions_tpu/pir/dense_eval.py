"""Fused multi-key selection-vector evaluation for dense PIR.

The reference server runs, per DPF key, a *full-domain* expansion of a
`2^ceil(log2(n))`-leaf tree whose leaves are 128-bit selection blocks
(`dense_dpf_pir_server.cc:92-127`) — even though only the first
`ceil(n/128)` blocks carry selection bits (the inner product stops at the
database size, `inner_product_hwy.cc:279-281`). Since the client puts the
query's block index in `alpha = index/128` (`dense_dpf_pir_client.cc:92-95`),
all the *useful* leaves live in the subtree under the all-zeros prefix of
depth `log_domain_size - ceil(log2(num_blocks))`.

The TPU pipeline exploits that: walk the all-zeros path down the shared
prefix (a `lax.scan` — one AES per key per level), then breadth-first
expand only the needed subtree (width-doubling, all keys batched), then hash
leaves to value blocks. Output is bit-identical to the reference's full
expansion restricted to the first `num_blocks` blocks, at ~1/128 of the AES
work for large domains.

All queries in a batch are evaluated together: seeds are stacked on a key
axis and correction words looked up per key, mirroring the per-seed
correction-word mode of `evaluate_prg_hwy.h:58-65`.
"""

from __future__ import annotations

import functools
import os
import subprocess
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import keys as fixed_keys
from ..dpf import DpfKey
from ..observability import tracing
from ..observability.device import default_telemetry
from ..utils.profiling import scoped
from ..ops import aes

U32 = jnp.uint32

_CLEAR_LSB = np.array(
    [0xFFFFFFFE, 0xFFFFFFFF, 0xFFFFFFFF, 0xFFFFFFFF], dtype=np.uint32
)


def donation_enabled() -> bool:
    """Whether single-device serving donates its per-request staged key
    tensors into the jit (`DPF_TPU_DONATE`, default on — the same knob
    and default as the mesh plan's scratch donation). Read at call time
    so tests can flip the env per arm."""
    return os.environ.get("DPF_TPU_DONATE", "1") != "0"


# On backends without donation support (CPU) every donated dispatch
# warns; the control arm is the env knob, not the warning stream. Same
# filter the mesh plan installs.
warnings.filterwarnings(
    "ignore", message=".*donated buffers were not usable.*"
)


def _evaluate_selection_blocks(
    seeds0: jnp.ndarray,
    control0: jnp.ndarray,
    cw_seeds: jnp.ndarray,
    cw_left: jnp.ndarray,
    cw_right: jnp.ndarray,
    last_vc: jnp.ndarray,
    *,
    walk_levels: int,
    expand_levels: int,
    num_blocks: int,
) -> jnp.ndarray:
    """Selection blocks for a batch of single-level 128-bit-XOR DPF keys.

    seeds0: uint32[nk, 4] root seeds; control0: uint32[nk] (= party);
    cw_seeds: uint32[L, nk, 4], cw_left/right: uint32[L, nk] with
    L = walk_levels + expand_levels (level-major for the scan);
    last_vc: uint32[nk, 4] last-level value correction.
    Returns uint32[nk, num_blocks, 4] selection blocks (the first
    `num_blocks` leaves of each key's tree).
    """
    seeds, control = _walk_zeros(
        seeds0, control0, cw_seeds[:walk_levels], cw_left[:walk_levels]
    )
    seeds, control = _expand_subtree(
        seeds, control, cw_seeds, cw_left, cw_right,
        first_level=walk_levels, num_levels=expand_levels,
    )
    sel = _leaf_blocks(seeds, control, last_vc)[:, :num_blocks, :]
    if sel.shape[1] < num_blocks:
        # num_blocks beyond the tree's 2^expand_levels leaf capacity can
        # only arise from padding the database rows (e.g. to a mesh-size
        # multiple): those rows are guaranteed all-zero, so zero selection
        # blocks serve them correctly.
        sel = jnp.pad(
            sel, ((0, 0), (0, num_blocks - sel.shape[1]), (0, 0))
        )
    return sel


evaluate_selection_blocks = functools.partial(
    jax.jit, static_argnames=("walk_levels", "expand_levels", "num_blocks")
)(scoped("pir.expand")(_evaluate_selection_blocks))

# Donating twin for the serving hot path: the six staged key tensors
# are freshly placed per batch (`stage_keys`) and dead after this call,
# so XLA may reuse their HBM for the selection matrix instead of
# holding both live. Deliberately a separate entry — the differential
# tests feed ONE staging to several implementations, which donation
# would invalidate — so only `DenseDpfPirServer` (via
# `donation_enabled()`) dispatches here.
evaluate_selection_blocks_donated = jax.jit(
    scoped("pir.expand")(_evaluate_selection_blocks),
    static_argnames=("walk_levels", "expand_levels", "num_blocks"),
    donate_argnums=(0, 1, 2, 3, 4, 5),
)


def _walk_zeros(seeds, control, cw_seeds_w, cw_left_w):
    """Walk the all-zeros prefix (left child each level): one `lax.scan`
    over the leading `walk_levels` correction words."""
    if cw_seeds_w.shape[0] == 0:
        return seeds, control
    clear = jnp.asarray(_CLEAR_LSB)

    def walk_body(carry, x):
        s, t = carry
        cw_s, cw_l = x  # [nk, 4], [nk]
        h = aes.mmo_hash(fixed_keys.RK_LEFT, s)
        h = h ^ jnp.where(t[:, None] != 0, cw_s, U32(0))
        t_new = h[:, 0] & U32(1)
        h = h & clear
        t_new = t_new ^ (t * cw_l)
        return (h, t_new), None

    (seeds, control), _ = lax.scan(
        walk_body, (seeds, control), (cw_seeds_w, cw_left_w)
    )
    return seeds, control


def _expand_subtree(
    seeds, control, cw_seeds, cw_left, cw_right, *, first_level, num_levels
):
    """Width-doubling expansion of the subtree, all keys batched.

    seeds: uint32[nk, 4] subtree roots -> uint32[nk, 2^num_levels, 4].
    Left and right children are produced by ONE key-selected AES pass per
    level (even lanes pick the left PRG key, odd lanes the right), halving
    the compiled graph size vs. two separate hashes — the TPU analog of
    the reference's per-lane key masking
    (`aes_128_fixed_key_hash_hwy.h:123-155`).
    """
    clear = jnp.asarray(_CLEAR_LSB)
    seeds = seeds[:, None, :]  # [nk, w, 4]
    control = control[:, None]  # [nk, w]
    for i in range(num_levels):
        lvl = first_level + i
        w = seeds.shape[1]
        cw_s = cw_seeds[lvl][:, None, :]  # [nk, 1, 4]
        cw_l = cw_left[lvl][:, None]
        cw_r = cw_right[lvl][:, None]
        doubled = jnp.repeat(seeds, 2, axis=1)  # [nk, 2w, 4]
        sel = jnp.tile(jnp.arange(2, dtype=U32), w)[None, :]  # [1, 2w]
        h = aes.mmo_hash_select(
            fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, sel, doubled
        )
        control2 = jnp.repeat(control, 2, axis=1)  # [nk, 2w]
        h = h ^ jnp.where(control2[..., None] != 0, cw_s, U32(0))
        t_new = h[..., 0] & U32(1)
        h = h & clear
        cw_dir = jnp.where(sel != 0, cw_r, cw_l)  # [nk, 2w]
        t_new = t_new ^ (control2 * cw_dir)
        seeds = h
        control = t_new
    return seeds, control


def _leaf_blocks(seeds, control, last_vc):
    """Leaf value blocks (output PRG + XOR value correction; party negation
    is the identity for XOR shares)."""
    v = aes.mmo_hash(fixed_keys.RK_VALUE, seeds)
    return v ^ jnp.where(control[..., None] != 0, last_vc[:, None, :], U32(0))


def expansion_impl():
    """The selection-block expansion implementation for the serving path.

    `DPF_TPU_EXPANSION`: `limb` — the per-level kernel re-entry above;
    `planes` — the plane-resident expansion
    (`dense_eval_planes.evaluate_selection_blocks_planes`, bit-identical,
    no per-level transposes); `v2` — the key-major layout-clean rewrite
    (`dense_eval_planes_v2`, natural-order exit here — the gather-free
    bitrev exit needs database coordination, see `serving_expansion`);
    `auto` (default) — planes on TPU, limb elsewhere (the plane path's
    win is VPU work; CPU compile times favor the limb path in the
    hermetic suite).
    """
    import functools
    import os

    from ..utils.runtime import planes_selected

    if os.environ.get("DPF_TPU_EXPANSION") == "v2":
        from .dense_eval_planes_v2 import (
            evaluate_selection_blocks_planes_v2,
        )

        return evaluate_selection_blocks_planes_v2
    if planes_selected("DPF_TPU_EXPANSION"):
        from .dense_eval_planes import evaluate_selection_blocks_planes

        if os.environ.get("DPF_TPU_EXPANSION") == "planes":
            # Explicitly forced: bypass the small-batch padding guard.
            return functools.partial(
                evaluate_selection_blocks_planes, force_planes=True
            )
        return evaluate_selection_blocks_planes
    return evaluate_selection_blocks


def serving_expansion():
    """(expansion fn, wants_bitrev) for the dense server's plain path.

    In `DPF_TPU_EXPANSION=v2` mode the server serves the gather-free
    exit: the expansion keeps its doubling-order leaves
    (`bitrev_leaves=True`) and the database runs the inner product
    against its bitrev-block staging — the caller passes
    `bitrev_blocks=True` through `inner_product_with`. Every other mode
    serves natural-order selections against the natural staging."""
    import os

    fn = expansion_impl()
    return fn, os.environ.get("DPF_TPU_EXPANSION") == "v2"


def selection_blocks_for_keys(dpf, keys: Sequence[DpfKey], num_blocks: int):
    """Evaluate a batch of single-level 128-bit-XOR DPF keys to the first
    `num_blocks` selection blocks.

    `dpf` supplies the tree depth; the walk/expand split is derived so only
    the covering subtree is expanded. Returns uint32[nk, num_blocks, 4].
    """
    total_levels = dpf._tree_levels_needed - 1
    expand_levels = min(max(0, (num_blocks - 1).bit_length()), total_levels)
    walk_levels = total_levels - expand_levels
    staged, device_walk = stage_keys_walked(keys, walk_levels)
    return expansion_impl()(
        *staged,
        walk_levels=device_walk,
        expand_levels=expand_levels,
        num_blocks=num_blocks,
    )


def stage_keys_walked(keys: Sequence[DpfKey], walk_levels: int):
    """Stage a key batch with the host-side zeros-walk applied when
    enabled (`DPF_TPU_HOST_WALK`, default on): returns `(staged,
    device_walk_levels)` where `device_walk_levels` is what the device
    step must still walk. Callers must pass the second element through —
    deriving it independently walks already-consumed correction words."""
    from ..utils.runtime import host_walk_enabled

    host_walk = walk_levels if host_walk_enabled() else 0
    return stage_keys(keys, host_walk_levels=host_walk), (
        walk_levels - host_walk
    )


_HOST_WALK_NATIVE_UNAVAILABLE = False


def warm_host_walk() -> None:
    """Build/load the native oracle outside the request path.

    The first `native.get_lib()` on a cold checkout spawns the g++ build
    (seconds); servers call this at construction so no live request pays
    it. A failure is remembered (the numpy walk serves instead) and
    warned about once."""
    global _HOST_WALK_NATIVE_UNAVAILABLE
    if _HOST_WALK_NATIVE_UNAVAILABLE:
        return
    try:
        from .. import native

        native.get_lib()
    except (
        ImportError,
        OSError,
        RuntimeError,
        subprocess.CalledProcessError,
    ) as e:
        _HOST_WALK_NATIVE_UNAVAILABLE = True
        warnings.warn(
            "native oracle unavailable for the host zeros-walk; "
            f"using the numpy path ({str(e).splitlines()[0][:120]})"
        )


def _walk_zeros_host(seeds0, control0, cw_seeds, cw_left, cw_right, levels):
    """Host-side twin of `_walk_zeros` (numpy in, numpy out).

    The device walk costs ~1.4 ms per 64-query batch — seven sequential
    bitsliced-AES levels on [nk, 4] arrays are pure dispatch latency on
    TPU — while the same ~nk*levels scalar AES calls take ~0.5 ms on the
    host, so staging walks the shared all-zeros prefix before the arrays
    ever reach the device. Uses the native C++ oracle when built, else
    the numpy MMO oracle. A failed native load is remembered (it spawns
    the g++ build) and warned about once — never retried per request,
    and genuine native-path errors are not masked."""
    warm_host_walk()
    if not _HOST_WALK_NATIVE_UNAVAILABLE:
        from .. import native

        sb = aes.limbs_to_bytes_np(seeds0)
        cw_b = aes.limbs_to_bytes_np(
            cw_seeds[:levels].reshape(-1, 4)
        ).reshape(levels, -1, 16)
        s, c = native.evaluate_seeds(
            sb,
            control0.astype(np.uint8),
            np.zeros_like(sb),
            cw_b,
            cw_left[:levels].astype(np.uint8),
            cw_right[:levels].astype(np.uint8),
            per_seed_cw=True,
        )
        return aes.bytes_to_limbs_np(s), c.astype(np.uint32)
    seeds = seeds0.copy()
    control = control0.copy()
    for lvl in range(levels):
        h = aes.mmo_hash_np(fixed_keys.RK_LEFT, seeds)
        h ^= np.where(control[:, None] != 0, cw_seeds[lvl], 0).astype(
            np.uint32
        )
        t_new = h[:, 0] & np.uint32(1)
        h &= _CLEAR_LSB
        control = t_new ^ (control * cw_left[lvl])
        seeds = h
    return seeds, control


def stage_keys_host(keys: Sequence[DpfKey], host_walk_levels: int = 0):
    """Host half of `stage_keys`: stack a batch of dense-PIR DPF keys
    into six numpy arrays without placing them on any device.

    Callers that serve from a mesh use this directly and do the
    placement themselves with a `NamedSharding` matching the step's
    in_specs (`ShardedServingPlan.stage_keys`), so keys never take a
    single-device detour before being resharded at dispatch.
    """
    nk = len(keys)
    num_levels = len(keys[0].correction_words)
    seeds0 = np.zeros((nk, 4), dtype=np.uint32)
    control0 = np.zeros((nk,), dtype=np.uint32)
    cw_seeds = np.zeros((num_levels, nk, 4), dtype=np.uint32)
    cw_left = np.zeros((num_levels, nk), dtype=np.uint32)
    cw_right = np.zeros((num_levels, nk), dtype=np.uint32)
    last_vc = np.zeros((nk, 4), dtype=np.uint32)
    for k, key in enumerate(keys):
        if len(key.correction_words) != num_levels:
            raise ValueError("all keys must have the same number of levels")
        if len(key.last_level_value_correction) != 1:
            raise ValueError("dense PIR keys carry exactly one leaf value")
        seeds0[k] = aes.u128_to_limbs(key.seed)
        control0[k] = key.party
        last_vc[k] = aes.u128_to_limbs(
            int(key.last_level_value_correction[0])
        )
        for lvl, cw in enumerate(key.correction_words):
            cw_seeds[lvl, k] = aes.u128_to_limbs(cw.seed)
            cw_left[lvl, k] = cw.control_left
            cw_right[lvl, k] = cw.control_right
    if host_walk_levels:
        if host_walk_levels > num_levels:
            raise ValueError(
                f"host_walk_levels={host_walk_levels} exceeds the keys' "
                f"{num_levels} correction-word levels"
            )
        seeds0, control0 = _walk_zeros_host(
            seeds0, control0, cw_seeds, cw_left, cw_right, host_walk_levels
        )
        cw_seeds = cw_seeds[host_walk_levels:]
        cw_left = cw_left[host_walk_levels:]
        cw_right = cw_right[host_walk_levels:]
    return seeds0, control0, cw_seeds, cw_left, cw_right, last_vc


def stage_keys(keys: Sequence[DpfKey], host_walk_levels: int = 0):
    """Stack a batch of dense-PIR DPF keys into device-ready arrays.

    All keys must have the same number of correction words and a single
    128-bit last-level value correction. With `host_walk_levels > 0` the
    shared all-zeros prefix is walked on the host during staging (see
    `_walk_zeros_host`): the returned seeds/control sit at that depth and
    the correction-word arrays drop the walked levels, so the device step
    runs with `walk_levels=0`.
    """
    with tracing.span("pir.stage_keys.host", record=False, keys=len(keys)):
        blocks = stage_keys_host(keys, host_walk_levels)
        # One device_put for the whole staging: all six blocks are
        # uint32, so they pack into a single flat transfer and slice
        # back apart on device (value_types.host_const's batching note,
        # applied). Six per-array transfers cost six dispatches on the
        # serving hot path; the TransferLedger counts this as one h2d
        # copy.
        flat = np.concatenate([b.ravel() for b in blocks])
    with tracing.span("pir.stage_keys.put", record=False, bytes=flat.nbytes):
        dev = default_telemetry().transfers.device_put(
            flat, phase="key_staging"
        )
    out = []
    offset = 0
    for b in blocks:
        out.append(dev[offset:offset + b.size].reshape(b.shape))
        offset += b.size
    return tuple(out)


def _chunked_pir_inner_products(
    seeds0: jnp.ndarray,
    control0: jnp.ndarray,
    cw_seeds: jnp.ndarray,
    cw_left: jnp.ndarray,
    cw_right: jnp.ndarray,
    last_vc: jnp.ndarray,
    db_words: jnp.ndarray,
    *,
    walk_levels: int,
    chunk_bits: int,
    chunk_expand_levels: int,
    num_chunks: int,
) -> jnp.ndarray:
    """Dense-PIR inner products with chunked expansion (long-context mode).

    For databases whose full selection tensor would outgrow HBM
    (`nq * num_blocks * 16` bytes), the covering subtree is processed in
    `num_chunks` chunks of `2^chunk_expand_levels` blocks: one `lax.scan`
    step walks the chunk root's path bits (`chunk_bits` levels), expands
    only that chunk's subtree, hashes its leaves, and XOR-accumulates the
    partial inner product against the chunk's record rows — so only one
    chunk's selections are ever live (the TPU analog of SURVEY.md §5's
    chunked/blockwise expansion sized to HBM).

    This is the legacy limb-layout fallback: `pir.planner` now routes
    over-budget serving to the streaming plane-layout pipeline
    (`dense_eval_planes_v2.streaming_pir_inner_products_v2`) when the
    expansion tree covers the padded block count, and only falls back
    here otherwise. The materialized path doubles as the differential
    oracle for both.

    db_words: uint32[num_chunks * 2^chunk_expand_levels * 128, W] (zero
    rows beyond the real record count). Tree depth must satisfy
    walk_levels + chunk_bits + chunk_expand_levels == total levels.
    Returns uint32[nk, W].
    """
    from ..ops.inner_product import xor_inner_product

    clear = jnp.asarray(_CLEAR_LSB)
    # Phase 1: walk the all-zeros shared prefix.
    seeds, control = _walk_zeros(
        seeds0, control0, cw_seeds[:walk_levels], cw_left[:walk_levels]
    )

    chunk_records = (1 << chunk_expand_levels) * 128
    num_words = db_words.shape[1]
    db_chunks = db_words.reshape(num_chunks, chunk_records, num_words)
    nk = seeds0.shape[0]

    def chunk_step(acc, xs):
        c, db_chunk = xs
        s, t = seeds, control

        # Phase 2a: walk this chunk root's path (bit j of c, MSB first).
        for j in range(chunk_bits):
            lvl = walk_levels + j
            bit = ((c >> (chunk_bits - 1 - j)) & 1).astype(U32)
            pbit = jnp.broadcast_to(bit, (nk,))
            h = aes.mmo_hash_select(
                fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, pbit, s
            )
            h = h ^ jnp.where(t[:, None] != 0, cw_seeds[lvl], U32(0))
            t_new = h[:, 0] & U32(1)
            h = h & clear
            cw_dir = jnp.where(pbit != 0, cw_right[lvl], cw_left[lvl])
            s, t = h, t_new ^ (t * cw_dir)

        # Phase 2b/3: expand the chunk subtree and hash its leaves.
        s, t = _expand_subtree(
            s, t, cw_seeds, cw_left, cw_right,
            first_level=walk_levels + chunk_bits,
            num_levels=chunk_expand_levels,
        )
        v = _leaf_blocks(s, t, last_vc)  # [nk, chunk_blocks, 4]
        # Phase 4: partial XOR inner product against this chunk's rows —
        # via the row-chunked kernel so the masked intermediate stays
        # bounded (256 rows at a time) regardless of chunk size.
        return acc ^ xor_inner_product(db_chunk, v), None

    acc0 = jnp.zeros((nk, num_words), dtype=U32)
    acc, _ = lax.scan(
        chunk_step,
        acc0,
        (jnp.arange(num_chunks, dtype=jnp.uint32), db_chunks),
    )
    return acc


_CHUNKED_STATIC = (
    "walk_levels", "chunk_bits", "chunk_expand_levels", "num_chunks"
)

chunked_pir_inner_products = functools.partial(
    jax.jit, static_argnames=_CHUNKED_STATIC
)(_chunked_pir_inner_products)

# Donating twin: the six per-request staged key tensors (args 0-5) are
# dead after the scan; `db_words` (arg 6) is the resident chunked
# database buffer and must NEVER be donated — a consumed database
# would force a full re-staging on the next request, which the
# TransferLedger test pins at zero.
chunked_pir_inner_products_donated = jax.jit(
    _chunked_pir_inner_products,
    static_argnames=_CHUNKED_STATIC,
    donate_argnums=(0, 1, 2, 3, 4, 5),
)
