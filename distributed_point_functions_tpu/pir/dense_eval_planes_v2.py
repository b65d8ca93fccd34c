"""Key-major plane-resident dense-PIR expansion (v2): the layout-clean
XLA path.

The v1 plane expansion (`dense_eval_planes.py`) keeps the lane axis
node-major/key-minor (lane = node * key_groups + key_group). That forces
two per-level layout materializations the r04 xprof blames for ~70% of
the serving expansion step (copies 8.1 ms + reshapes 5.25 ms +
concatenates 2.3 ms of a 23.1 ms step):

* per-key correction operands must be broadcast *periodically* along the
  minor lane axis (`_tile_keys`: a `jnp.tile` = broadcast + reshape whose
  intermediate has the tiny key-group count in a tiled dimension — a
  pad-heavy relayout materializing a state-sized array every level), and
* the exit permutation back to natural block order is a state-sized
  gather every batch.

v2 removes both by construction:

* **Key-group axis leading.** State is `uint32[kg, 16, 8, W]` (kg =
  padded_keys/32, W = subtree width): the tiled physical dims are always
  (8, W), so no shape in the level loop carries a padded tile, and every
  per-key operand (`[kg, 16, 8, 1]` seed corrections, `[kg, 1]`
  direction words) broadcasts along the minor W axis **natively** — zero
  materialized operands. The plane ops (`sigma_planes`,
  `aes_rounds_planes`, `mmo_hash_planes`) are elementwise over the
  trailing lane axis, so `jax.vmap` over the leading kg axis reuses them
  unchanged.
* **No exit gather in serving mode.** Leaves exit in the doubling
  (bit-reversed) order; `bitrev_leaves=True` hands them to the inner
  product as-is, and the serving side bit-reversal-permutes the
  database's record *blocks* once at staging (`bitrev_permutation` is an
  involution), so per-batch cost is zero. `bitrev_leaves=False` applies
  the natural-order gather for bit-identity with
  `dense_eval.evaluate_selection_blocks` (differential tests).

Reference semantics: `ExpandSeeds` breadth-first buffer reuse
(`dpf/distributed_point_function.cc:289-372`) restricted to the covering
subtree; the [all-left; all-right] append per level is the same
recurrence per key pyramid, so the per-key leaf order is the classic
bit-reversal, exactly as v1's pure-XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import keys as fixed_keys
from ..ops.aes_bitslice import (
    aes_rounds_planes,
    limbs_to_planes,
    mmo_hash_planes,
    planes_to_limbs,
    sigma_planes,
)
from ..ops.inner_product import varying_zeros, xor_inner_product_accumulate
from ..ops.inner_product_pallas import xor_inner_product_pallas2_accumulate
from .dense_eval import _walk_zeros
from .dense_eval_planes import (
    bitrev_permutation,
    pack_key_bits,
    pack_key_planes,
)

U32 = jnp.uint32

_sigma_v = jax.vmap(sigma_planes)
_aes_v = jax.vmap(aes_rounds_planes, in_axes=(None, 0))
_mmo_v = jax.vmap(mmo_hash_planes, in_axes=(None, 0))


def pack_key_planes_kg(cw: jnp.ndarray) -> jnp.ndarray:
    """uint32[nkp, 4] per-key 128-bit words -> uint32[kg, 16, 8, 1]
    key-major plane masks (native broadcast operand along W)."""
    return jnp.moveaxis(pack_key_planes(cw), -1, 0)[..., None]


def expand_level_planes_v2(state, ctrl, cw_p, cwl_w, cwr_w):
    """One [all-left; all-right] doubling level in key-major layout.

    state: uint32[kg, 16, 8, W] planes; ctrl: uint32[kg, W] packed parent
    control bits (word [k, n] = keys 32k..32k+31 at node n); cw_p:
    uint32[kg, 16, 8, 1] seed-correction planes; cwl_w / cwr_w:
    uint32[kg, 1] packed direction-correction words. Returns
    (state [kg, 16, 8, 2W], ctrl [kg, 2W])."""
    sig = _sigma_v(state)
    left = _aes_v(fixed_keys.RK_LEFT, sig) ^ sig
    right = _aes_v(fixed_keys.RK_RIGHT, sig) ^ sig
    st = jnp.concatenate([left, right], axis=-1)
    ctrl2 = jnp.concatenate([ctrl, ctrl], axis=-1)
    st = st ^ (cw_p & ctrl2[:, None, None, :])
    t_new = st[:, 0, 0]  # LSB plane = control bits
    st = st.at[:, 0, 0].set(jnp.zeros_like(t_new))
    w = ctrl.shape[-1]
    kg = ctrl.shape[0]
    cw_dir = jnp.concatenate(
        [
            jnp.broadcast_to(cwl_w, (kg, w)),
            jnp.broadcast_to(cwr_w, (kg, w)),
        ],
        axis=-1,
    )
    return st, t_new ^ (ctrl2 & cw_dir)


def _pad_keys32(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc):
    """Pad the per-key operand set to a 32-multiple of keys (the plane
    packing granule). Padded keys expand to garbage-but-deterministic
    leaves; callers slice results back to the real key count."""
    nk = seeds0.shape[0]
    pad_keys = (-nk) % 32
    if pad_keys:
        seeds0 = jnp.pad(seeds0, ((0, pad_keys), (0, 0)))
        control0 = jnp.pad(control0, ((0, pad_keys),))
        cw_seeds = jnp.pad(cw_seeds, ((0, 0), (0, pad_keys), (0, 0)))
        cw_left = jnp.pad(cw_left, ((0, 0), (0, pad_keys)))
        cw_right = jnp.pad(cw_right, ((0, 0), (0, pad_keys)))
        last_vc = jnp.pad(last_vc, ((0, pad_keys), (0, 0)))
    return seeds0, control0, cw_seeds, cw_left, cw_right, last_vc


def _planes_leaves_to_blocks(values: jnp.ndarray) -> jnp.ndarray:
    """Leave plane space once: value planes [kg, 16, 8, w] ->
    packed selection blocks [kg*32, w, 4] (leaf axis order preserved)."""
    kg = values.shape[0]
    w = values.shape[-1]
    lim = jax.vmap(planes_to_limbs)(values)  # [kg, w*32, 4]
    lim = lim.reshape(kg, w, 32, 4)
    out = jnp.moveaxis(lim, 0, 1).reshape(w, kg * 32, 4)
    return jnp.moveaxis(out, 0, 1)  # [kg*32, w, 4]


def evaluate_selection_blocks_planes_v2(
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
    bitrev_leaves: bool = False,
) -> jnp.ndarray:
    """Drop-in for `dense_eval.evaluate_selection_blocks` (bit-identical
    output with `bitrev_leaves=False`), computed with the key-major
    plane expansion.

    With `bitrev_leaves=True` the leaf axis stays in doubling order
    (natural block g at position bitrev(g)) and is NOT truncated to
    `num_blocks` — for serving paths that bit-reverse the database's
    record blocks at staging instead.
    """
    nk = seeds0.shape[0]
    seeds0, control0, cw_seeds, cw_left, cw_right, last_vc = _pad_keys32(
        seeds0, control0, cw_seeds, cw_left, cw_right, last_vc
    )

    # Phase 1 (limb space, [nk, 4] only): walk the all-zeros prefix.
    seeds, control = _walk_zeros(
        seeds0, control0, cw_seeds[:walk_levels], cw_left[:walk_levels]
    )

    # Enter key-major plane space once: [kg, 16, 8, 1].
    state = jnp.moveaxis(limbs_to_planes(seeds), -1, 0)[..., None]
    ctrl = pack_key_bits(control.astype(U32))[:, None]  # [kg, 1]

    for i in range(expand_levels):
        lvl = walk_levels + i
        state, ctrl = expand_level_planes_v2(
            state,
            ctrl,
            pack_key_planes_kg(cw_seeds[lvl]),
            pack_key_bits(cw_left[lvl])[:, None],
            pack_key_bits(cw_right[lvl])[:, None],
        )

    # Leaf value blocks: output PRG + XOR value correction (party
    # negation is the identity for XOR shares).
    values = _mmo_v(fixed_keys.RK_VALUE, state)
    values = values ^ (pack_key_planes_kg(last_vc) & ctrl[:, None, None, :])

    out = _planes_leaves_to_blocks(values)  # [nkp, w, 4]
    if not bitrev_leaves:
        perm = jnp.asarray(bitrev_permutation(expand_levels))
        out = out[:, perm, :][:, :num_blocks, :]
        if out.shape[1] < num_blocks:
            # Blocks beyond the tree's capacity (mesh-padded databases)
            # can only select guaranteed-zero rows.
            out = jnp.pad(
                out, ((0, 0), (0, num_blocks - out.shape[1]), (0, 0))
            )
    return out[:nk]


def bitrev_block_permute_records(db_host: np.ndarray) -> np.ndarray:
    """Bit-reversal-permute a record-major database's 128-record blocks
    (host-side, once at staging) so a `bitrev_leaves=True` expansion's
    selection vector lines up with it. The permutation is an involution;
    responses are XOR-sums over (selection, record) pairs, so applying
    the same permutation to both sides leaves every response unchanged.
    """
    num_records = db_host.shape[0]
    if num_records % 128:
        raise ValueError("record count must be padded to a multiple of 128")
    num_blocks = num_records // 128
    levels = max(0, (num_blocks - 1).bit_length())
    if num_blocks != 1 << levels:
        raise ValueError("block count must be a power of two")
    perm = bitrev_permutation(levels)
    return (
        db_host.reshape(num_blocks, 128, -1)[perm]
        .reshape(num_records, -1)
    )


# ---------------------------------------------------------------------------
# Streaming fused expand -> inner-product serving pipeline.
#
# The covering subtree is expanded down to `cut_levels` once; the last
# `chunk_levels` doubling levels then run inside a jitted `lax.scan`, one
# tail subtree (= one cut-state lane) per step, and each step's selection
# blocks are XOR/MXU-accumulated against the matching database block span
# immediately.  The full `uint32[num_queries, num_blocks, 4]` selection
# matrix never exists in HBM, and XLA double-buffers the next database
# chunk read against the current tail expansion.
#
# Block order.  After `cut` doubling levels, cut-state lane c holds the
# node whose natural cut-bit prefix is bitrev_cut(c); expanding that lane
# alone `r` more levels emits sub-leaf position q holding natural
# sub-index bitrev_r(q).  Scan step c therefore covers natural blocks
#     (bitrev_cut(c) << r) | bitrev_r(q),  q = 0..2^r-1,
# which is NOT a contiguous span of the full-bitrev staging (a contiguous
# full-bitrev span is a set of leaves sharing a path *suffix*, scattered
# across all tail subtrees).  The database is instead staged once in this
# *blocked* bit-reversed block order (`streaming_block_order`, an
# involution that degenerates to the plain bit-reversal when cut == 0 or
# r == 0), so every scan step reads one contiguous chunk.
# ---------------------------------------------------------------------------


def streaming_block_order(expand_levels: int, cut_levels: int) -> np.ndarray:
    """Natural block index held at each staged position of the streaming
    database layout: position c * 2^r + q (scan step c, row-block q)
    holds natural block (bitrev_cut(c) << r) | bitrev_r(q), with
    r = expand_levels - cut_levels."""
    if not 0 <= cut_levels <= expand_levels:
        raise ValueError("cut_levels must be in [0, expand_levels]")
    r = expand_levels - cut_levels
    pre = np.asarray(bitrev_permutation(cut_levels), dtype=np.int64)
    sub = np.asarray(bitrev_permutation(r), dtype=np.int64)
    return ((pre[:, None] << r) | sub[None, :]).reshape(-1)


def streaming_block_permute_records(
    db_host: np.ndarray, cut_levels: int
) -> np.ndarray:
    """Permute a record-major database's 128-record blocks into streaming
    block order (host-side, once at staging). Row count must already be
    padded to a power-of-two block count covering the tree."""
    num_records = db_host.shape[0]
    if num_records % 128:
        raise ValueError("record count must be padded to a multiple of 128")
    num_blocks = num_records // 128
    levels = max(0, (num_blocks - 1).bit_length())
    if num_blocks != 1 << levels:
        raise ValueError("block count must be a power of two")
    order = streaming_block_order(levels, cut_levels)
    return (
        db_host.reshape(num_blocks, 128, -1)[order]
        .reshape(num_records, -1)
    )


def _packed_levels(cw_seeds, cw_left, cw_right, lo: int, hi: int):
    """Pre-pack per-level correction operands for doubling levels
    [lo, hi) into key-major plane form (kept outside scan bodies so the
    packing is not re-traced per step)."""
    cwp = [pack_key_planes_kg(cw_seeds[lvl]) for lvl in range(lo, hi)]
    cwl = [pack_key_bits(cw_left[lvl])[:, None] for lvl in range(lo, hi)]
    cwr = [pack_key_bits(cw_right[lvl])[:, None] for lvl in range(lo, hi)]
    return cwp, cwl, cwr


def streaming_cut_state(
    seeds0,
    control0,
    cw_seeds,
    cw_left,
    cw_right,
    *,
    walk_levels: int,
    cut_levels: int,
):
    """Walk the all-zeros prefix and expand the covering subtree down to
    the cut: the resumable state the streaming scan slices per step.

    Operands must already be 32-multiple padded (`_pad_keys32`). Returns
    (state [kg, 16, 8, 2^cut], ctrl [kg, 2^cut])."""
    seeds, control = _walk_zeros(
        seeds0, control0, cw_seeds[:walk_levels], cw_left[:walk_levels]
    )
    state = jnp.moveaxis(limbs_to_planes(seeds), -1, 0)[..., None]
    ctrl = pack_key_bits(control.astype(U32))[:, None]
    cwp, cwl, cwr = _packed_levels(
        cw_seeds, cw_left, cw_right, walk_levels, walk_levels + cut_levels
    )
    for level in range(cut_levels):
        state, ctrl = expand_level_planes_v2(
            state, ctrl, cwp[level], cwl[level], cwr[level]
        )
    return state, ctrl


def streaming_tail_selections(state, ctrl, tail_cwp, tail_cwl, tail_cwr, vc_p):
    """Resumable tail expansion: finish one tail subtree from its
    cut-level state slice and emit its packed selection blocks.

    state [kg, 16, 8, n] / ctrl [kg, n] (n = 1 inside the scan),
    tail_* are `_packed_levels` lists, vc_p = `pack_key_planes_kg` of
    the value correction. Returns uint32[kg*32, n << len(tail_cwp), 4]
    in single-subtree doubling (bit-reversed) leaf order."""
    for cwp, cwl, cwr in zip(tail_cwp, tail_cwl, tail_cwr):
        state, ctrl = expand_level_planes_v2(state, ctrl, cwp, cwl, cwr)
    values = _mmo_v(fixed_keys.RK_VALUE, state)
    values = values ^ (vc_p & ctrl[:, None, None, :])
    return _planes_leaves_to_blocks(values)


def streaming_scan_accumulate(
    state,
    ctrl,
    db_chunks,
    tail_cwp,
    tail_cwl,
    tail_cwr,
    vc_p,
    *,
    ip: str = "jnp",
    interpret: bool = False,
    vma=(),
):
    """Scan the cut-state lanes against the streaming-staged database
    chunks, fusing tail expansion with the XOR inner product.

    db_chunks: uint32[n, chunk_records, W] row-major (ip="jnp") or
    uint32[n, 32, Gc, W] bit-major (ip="pallas2"), where n matches the
    lane count of `state`. Returns uint32[kg*32, W] accumulators."""
    num_lanes = state.shape[-1]
    if db_chunks.shape[0] != num_lanes:
        raise ValueError(
            f"db_chunks leading axis {db_chunks.shape[0]} != cut-state "
            f"lane count {num_lanes}"
        )
    st_x = jnp.moveaxis(state, -1, 0)[..., None]  # [n, kg, 16, 8, 1]
    ct_x = jnp.moveaxis(ctrl, -1, 0)[..., None]  # [n, kg, 1]

    def body(acc, xs):
        db_c, st, ct = xs
        sel = streaming_tail_selections(
            st, ct, tail_cwp, tail_cwl, tail_cwr, vc_p
        )
        if ip == "pallas2":
            acc = xor_inner_product_pallas2_accumulate(
                acc, db_c, sel, interpret=interpret, vma=vma
            )
        else:
            acc = xor_inner_product_accumulate(acc, db_c, sel)
        return acc, None

    nkp = state.shape[0] * 32
    acc0 = varying_zeros((nkp, db_chunks.shape[-1]), st_x, ct_x, db_chunks)
    acc, _ = jax.lax.scan(body, acc0, (db_chunks, st_x, ct_x))
    return acc


@functools.partial(
    jax.jit,
    static_argnames=("walk_levels", "cut_levels", "chunk_levels", "ip", "interpret"),
)
def streaming_pir_inner_products_v2(
    seeds0,
    control0,
    cw_seeds,
    cw_left,
    cw_right,
    last_vc,
    db_chunks,
    *,
    walk_levels: int,
    cut_levels: int,
    chunk_levels: int,
    ip: str = "jnp",
    interpret: bool = False,
):
    """One jitted streaming serving step: expansion fused with the XOR
    inner product, never materializing the selection matrix.

    The database must be staged in streaming block order
    (`streaming_block_permute_records` with the same `cut_levels`) and
    split into `2^cut_levels` chunks along the leading axis — bit-major
    per chunk for ip="pallas2". Returns uint32[num_keys, W] XOR-share
    inner products, bit-identical to the materialized path."""
    levels = walk_levels + cut_levels + chunk_levels
    if cw_seeds.shape[0] != levels:
        raise ValueError(
            f"key has {cw_seeds.shape[0]} correction levels; plan needs "
            f"walk {walk_levels} + cut {cut_levels} + chunk {chunk_levels}"
        )
    if db_chunks.shape[0] != 1 << cut_levels:
        raise ValueError(
            f"expected {1 << cut_levels} database chunks, got "
            f"{db_chunks.shape[0]}"
        )
    nk = seeds0.shape[0]
    seeds0, control0, cw_seeds, cw_left, cw_right, last_vc = _pad_keys32(
        seeds0, control0, cw_seeds, cw_left, cw_right, last_vc
    )
    state, ctrl = streaming_cut_state(
        seeds0,
        control0,
        cw_seeds,
        cw_left,
        cw_right,
        walk_levels=walk_levels,
        cut_levels=cut_levels,
    )
    tail_cwp, tail_cwl, tail_cwr = _packed_levels(
        cw_seeds, cw_left, cw_right, walk_levels + cut_levels, levels
    )
    vc_p = pack_key_planes_kg(last_vc)
    acc = streaming_scan_accumulate(
        state,
        ctrl,
        db_chunks,
        tail_cwp,
        tail_cwl,
        tail_cwr,
        vc_p,
        ip=ip,
        interpret=interpret,
    )
    return acc[:nk]
