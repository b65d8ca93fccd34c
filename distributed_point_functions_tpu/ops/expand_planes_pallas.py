"""Pallas TPU kernels for the plane-resident DPF expansion levels.

The XLA version of one expansion level (`pir/dense_eval_planes.py:
expand_level_planes`) runs the ~2000-gate bitsliced AES circuit as jnp
ops on `[16, 8, G]` plane tensors; every stack/reshape/fusion break
materializes the full state to HBM, and at the headline config the
measured cost (~8 ms per 64-query batch) is ~12x the VPU gate-work
roofline (~0.7 ms) — the level is HBM-bound on intermediates, not
compute-bound. These kernels run a whole level in VMEM per lane tile:

* `expand_level_planes_pallas` — sigma, BOTH fixed-key AES applications
  (left/right children), seed correction under the parent control mask,
  LSB extract/clear, and the direction-correction of the control bits,
  one input read + two output writes of HBM traffic per level;
* `value_hash_planes_pallas` — the leaf MMO output hash + value
  correction the same way.

Round keys are baked in as `[16, 8, 1]` all-ones/zeros constant masks
per round (fixed-key AES: AddRoundKey is XOR with a constant plane).
Per-key correction planes stay packed at `[16, 8, KG]` (KG = keys/32)
and are tiled across the node-major lane axis in VMEM via
`pltpu.repeat` — the lane layout guarantees lane = node * KG + keyword,
so a whole-array tile repeats every KG lanes.

Everything is differentially tested against the XLA twins in interpret
mode (`tests/test_expand_pallas.py`); the TPU auto set is AOT-compiled
for v5e in `tests/test_chip_compile.py` and bit-checked once on the
device before serving (`pir/dense_eval_planes.py`; a mismatch raises).

Reference semantics: `ExpandSeeds`
(`dpf/distributed_point_function.cc:289-372`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import keys as fixed_keys
from . import aes as _aes
from .aes_bitslice import _mix_columns_planes, _rk_bits, _sub_bytes_planes

U32 = jnp.uint32

_SHIFT_ROWS = list(_aes._SHIFT_ROWS)

# Default lane tile: [16, 8, 1024] u32 state = 512 KB; the kernel's
# working set (sigma + two AES states + temporaries) stays well under
# VMEM at this width.
_TILE_LANES = 1024


def _rk_masks(round_keys: np.ndarray) -> np.ndarray:
    """uint8[11, 16] schedule -> uint32[11, 16, 8, 1] all-ones/zeros
    plane masks (AddRoundKey with a fixed key = XOR with constants)."""
    bits = _rk_bits(round_keys).astype(np.uint32)  # [11, 16, 8]
    return (bits * np.uint32(0xFFFFFFFF))[..., None]

_MASKS_LEFT = _rk_masks(fixed_keys.RK_LEFT)
_MASKS_RIGHT = _rk_masks(fixed_keys.RK_RIGHT)
_MASKS_VALUE = _rk_masks(fixed_keys.RK_VALUE)
_MASKS_LR = np.stack([_MASKS_LEFT, _MASKS_RIGHT])  # [2, 11, 16, 8, 1]


def _shift_rows_static(state: jnp.ndarray) -> jnp.ndarray:
    """Byte-axis permutation as static slices + one concat (avoids a
    gather, which Mosaic may not lower)."""
    return jnp.concatenate(
        [state[j : j + 1] for j in _SHIFT_ROWS], axis=0
    )


def _aes_fixed_planes(masks: jnp.ndarray, state: jnp.ndarray) -> jnp.ndarray:
    """AES-128 rounds on [16, 8, T] planes; `masks` is the [11, 16, 8, 1]
    round-key plane-mask array (a kernel input: Pallas forbids captured
    array constants)."""
    state = state ^ masks[0]
    for rnd in range(1, 10):
        state = _sub_bytes_planes(state)
        state = _shift_rows_static(state)
        state = _mix_columns_planes(state)
        state = state ^ masks[rnd]
    state = _sub_bytes_planes(state)
    state = _shift_rows_static(state)
    return state ^ masks[10]


def _sigma(state: jnp.ndarray) -> jnp.ndarray:
    lo = state[:8]
    hi = state[8:]
    return jnp.concatenate([hi, hi ^ lo], axis=0)


def _aes_select_planes(
    masks: jnp.ndarray, selb: jnp.ndarray, sig: jnp.ndarray
) -> jnp.ndarray:
    """Select-key AES rounds + MMO feed-forward on [16, 8, T] planes:
    each lane's round keys come from `masks[0]` (left) or `masks[1]`
    (right) per its `selb` mask word — the per-lane key select of
    `dpf/internal/aes_128_fixed_key_hash_hwy.h:123-155`. Shared by the
    path-walk and walk-descent kernels."""

    def ark(st, rnd):
        m0 = masks[0, rnd]
        m1 = masks[1, rnd]
        return st ^ ((m0 & ~selb) | (m1 & selb))

    st = ark(sig, 0)
    for rnd in range(1, 10):
        st = _sub_bytes_planes(st)
        st = _shift_rows_static(st)
        st = _mix_columns_planes(st)
        st = ark(st, rnd)
    st = _sub_bytes_planes(st)
    st = _shift_rows_static(st)
    return ark(st, 10) ^ sig


def _zero_lsb_plane(state: jnp.ndarray) -> jnp.ndarray:
    """state with plane [0, 0] (the seed LSB = embedded control bit)
    zeroed, built from static slices + leading-axis concatenates:
    `.at[0, 0].set(...)` lowers to a scatter, which Mosaic rejects
    ('Unimplemented primitive in Pallas TPU lowering: scatter')."""
    zero = jnp.zeros_like(state[0, 0])
    row0 = jnp.concatenate([zero[None], state[0, 1:]], axis=0)
    return jnp.concatenate([row0[None], state[1:]], axis=0)


def _level_kernel(
    state_ref,
    ctrl_ref,
    cwp_ref,
    cwl_ref,
    cwr_ref,
    masks_ref,
    outl_ref,
    outr_ref,
    ctl_ref,
    ctr_ref,
    *,
    reps: int,
):
    sig = _sigma(state_ref[:])
    masks = masks_ref[:]  # [2, 11, 16, 8, 1]: left/right round-key planes
    left = _aes_fixed_planes(masks[0], sig) ^ sig
    right = _aes_fixed_planes(masks[1], sig) ^ sig

    ctrl = ctrl_ref[:]  # [1, T] packed parent control bits
    cwp = pltpu.repeat(cwp_ref[:], reps, axis=2)  # [16, 8, T]
    mask = cwp & ctrl[0][None, None, :]
    left = left ^ mask
    right = right ^ mask

    t_left = left[0, 0]  # LSB plane = child control bits
    t_right = right[0, 0]
    outl_ref[:] = _zero_lsb_plane(left)
    outr_ref[:] = _zero_lsb_plane(right)

    cwl = pltpu.repeat(cwl_ref[:], reps, axis=1)  # [1, T]
    cwr = pltpu.repeat(cwr_ref[:], reps, axis=1)
    ctl_ref[:] = (t_left ^ (ctrl[0] & cwl[0]))[None, :]
    ctr_ref[:] = (t_right ^ (ctrl[0] & cwr[0]))[None, :]


def _check_tile(tile: int, g: int, kg: int) -> None:
    """Fail fast on an illegal forced tile: every chunk width (tile, and
    the g % tile remainder if the probe/test caller passes one that does
    not divide g) must be a positive multiple of kg, or the in-kernel
    correction repeat silently truncates and dies in an opaque mid-trace
    broadcast error."""
    widths = {min(tile, g)} if tile > 0 else {0}
    if 0 < tile < g and g % tile:
        widths.add(g % tile)
    if tile <= 0 or any(w <= 0 or w % kg for w in widths):
        raise ValueError(
            f"tile_lanes={tile} must be a positive multiple of the key "
            f"group count {kg} (lanes={g}), as must any remainder chunk"
        )


def _pick_tile(
    num_lanes: int, key_groups: int, cap: int = _TILE_LANES
) -> int:
    tile = min(cap, num_lanes)
    while tile > key_groups and (
        num_lanes % tile != 0 or tile % key_groups != 0
    ):
        tile //= 2
    if num_lanes % tile != 0 or tile % key_groups != 0:
        tile = num_lanes
    return tile


# Walk-descent default tile: the working set is ~6 copies of a
# [16, 8, tile] u32 state (~6 MB at 2048) and the hardware probe
# validates the 2048-lane geometry, so the serving default matches it.
_WALK_TILE_LANES = 2048


def pick_walk_tile(
    w: int, kg: int, node_lanes: int, compact_entry: bool, r: int
) -> int:
    """The walk-descent wrapper's default tile choice, exposed so
    callers that must compose the exit order (which depends on the
    tile in compact mode) can compute the same value."""
    if not compact_entry:
        return _pick_tile(w, kg, cap=_WALK_TILE_LANES)
    # Compact tiles must cover whole node blocks; pick the largest
    # multiple of node_lanes<<r within the cap, or the whole width
    # when one block alone exceeds the cap.
    block = node_lanes << r
    tile = min(w, max(block, (_WALK_TILE_LANES // block) * block))
    while w % tile:
        tile -= block
    return tile


def walk_plan(
    w: int, kg: int, node_lanes: int, r: int, want_compact: bool
) -> tuple:
    """(tile, compact, nodes_per_tile) for one walk phase — the ONE
    place the tile/mode decision lives, so the kernel call and the
    exit-order composition can never disagree. Compact is declined
    when a single node block (node_lanes << r lanes) exceeds the tile
    cap: the compact tile would blow the probed VMEM envelope and fail
    a compile the replicated mode (which tiles freely) survives."""
    block = node_lanes << r
    if want_compact and block <= _WALK_TILE_LANES:
        tile = pick_walk_tile(w, kg, node_lanes, True, r)
        return tile, True, (tile >> r) // node_lanes
    return pick_walk_tile(w, kg, node_lanes, False, r), False, 0


def compose_walk_leaf_order(
    entry_order: np.ndarray, r: int, compact: bool, nodes_per_tile: int
) -> np.ndarray:
    """Exit leaf order of a walk phase planned by `walk_plan`: natural
    per-node offsets (replicated mode) or offset-major tiles (compact),
    composed over the entry order."""
    if compact:
        return walk_compact_leaf_order(entry_order, r, nodes_per_tile)
    m = np.asarray(entry_order, dtype=np.int64)
    return (
        m[:, None] * (1 << r) + np.arange(1 << r, dtype=np.int64)[None, :]
    ).reshape(-1)


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_lanes")
)
def expand_level_planes_pallas(
    state: jnp.ndarray,
    ctrl: jnp.ndarray,
    cwp_kg: jnp.ndarray,
    cwl_kg: jnp.ndarray,
    cwr_kg: jnp.ndarray,
    interpret: bool = False,
    tile_lanes: int | None = None,
):
    """One [all-left; all-right] expansion level, fused in VMEM.

    state: uint32[16, 8, G]; ctrl: uint32[G] packed parent control bits;
    cwp_kg: uint32[16, 8, KG] per-key seed-correction planes
    (`pack_key_planes`); cwl_kg / cwr_kg: uint32[KG] packed per-key
    direction-correction bits. Returns (state [16, 8, 2G], ctrl [2G])
    in [all-left; all-right] child order — the same contract as
    `dense_eval_planes.expand_level_planes` with untiled corrections.
    """
    _, _, g = state.shape
    kg = cwp_kg.shape[-1]
    tile = _pick_tile(g, kg) if tile_lanes is None else tile_lanes
    _check_tile(tile, g, kg)
    ctrl2 = ctrl[None, :]
    cwl2 = cwl_kg[None, :]
    cwr2 = cwr_kg[None, :]

    def call(state_c, ctrl_c):
        # One grid-(1,) pallas_call per lane chunk: multi-step lane grids
        # crash tpu_compile_helper on v5e (expand_profile 2026-07-31:
        # fine through G=1024 = one grid step, exit-1 at G=2048 = two),
        # so the chunking lives here in XLA instead of in the grid.
        t = state_c.shape[-1]
        reps = t // kg  # a chunk can be narrower than the nominal tile
        out_shapes = (
            jax.ShapeDtypeStruct((16, 8, t), U32),
            jax.ShapeDtypeStruct((16, 8, t), U32),
            jax.ShapeDtypeStruct((1, t), U32),
            jax.ShapeDtypeStruct((1, t), U32),
        )
        return pl.pallas_call(
            functools.partial(_level_kernel, reps=reps),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((16, 8, kg), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, kg), lambda l: (0, 0)),
                pl.BlockSpec((1, kg), lambda l: (0, 0)),
                pl.BlockSpec(
                    (2, 11, 16, 8, 1), lambda l: (0, 0, 0, 0, 0)
                ),
            ],
            out_specs=(
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
            ),
            out_shape=out_shapes,
            interpret=interpret,
        )(state_c, ctrl_c, cwp_kg, cwl2, cwr2, _MASKS_LR)

    ls, rs, lc, rc = [], [], [], []
    for lo in range(0, g, tile):
        outl, outr, ctl, ctr = call(
            state[:, :, lo : lo + tile], ctrl2[:, lo : lo + tile]
        )
        ls.append(outl)
        rs.append(outr)
        lc.append(ctl[0])
        rc.append(ctr[0])
    # Global [all-left; all-right] child order across chunks.
    new_state = jnp.concatenate(ls + rs, axis=-1)
    new_ctrl = jnp.concatenate(lc + rc)
    return new_state, new_ctrl


def _value_kernel(state_ref, ctrl_ref, vc_ref, masks_ref, out_ref, *,
                  reps: int):
    sig = _sigma(state_ref[:])
    values = _aes_fixed_planes(masks_ref[:], sig) ^ sig
    vc = pltpu.repeat(vc_ref[:], reps, axis=2)
    out_ref[:] = values ^ (vc & ctrl_ref[:][0][None, None, :])


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_lanes")
)
def value_hash_planes_pallas(
    state: jnp.ndarray,
    ctrl: jnp.ndarray,
    vc_kg: jnp.ndarray,
    interpret: bool = False,
    tile_lanes: int | None = None,
) -> jnp.ndarray:
    """Leaf MMO output hash + value correction, fused in VMEM.

    state: uint32[16, 8, G]; ctrl: uint32[G]; vc_kg: uint32[16, 8, KG]
    per-key value-correction planes. Returns uint32[16, 8, G] — same
    math as `mmo_hash_planes(RK_VALUE, state) ^ (vc_tiled & ctrl)`.
    """
    _, _, g = state.shape
    kg = vc_kg.shape[-1]
    tile = _pick_tile(g, kg) if tile_lanes is None else tile_lanes
    _check_tile(tile, g, kg)
    ctrl2 = ctrl[None, :]
    masks = jnp.asarray(_MASKS_VALUE)

    def call(state_c, ctrl_c):
        # Grid-(1,) per lane chunk, like `expand_level_planes_pallas`:
        # multi-step lane grids crash tpu_compile_helper on v5e.
        t = state_c.shape[-1]
        reps = t // kg  # a chunk can be narrower than the nominal tile
        return pl.pallas_call(
            functools.partial(_value_kernel, reps=reps),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((16, 8, kg), lambda l: (0, 0, 0)),
                pl.BlockSpec((11, 16, 8, 1), lambda l: (0, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 8, t), U32),
            interpret=interpret,
        )(state_c, ctrl_c, vc_kg, masks)

    return jnp.concatenate(
        [
            call(state[:, :, lo : lo + tile], ctrl2[:, lo : lo + tile])
            for lo in range(0, g, tile)
        ],
        axis=-1,
    )


def _tail_kernel(
    state_ref,
    ctrl_ref,
    cwp_ref,
    cwl_ref,
    cwr_ref,
    vc_ref,
    masks_lr_ref,
    masks_v_ref,
    out_ref,
    outc_ref,
    *,
    kg: int,
    r: int,
):
    """Expand one lane tile through the last `r` levels AND the leaf
    value hash entirely in VMEM: one HBM read of the [16, 8, T] entry
    tile, one HBM write of the [16, 8, T * 2^r] value planes.

    Subtrees of distinct tiles are independent, so each tile runs the
    whole tail alone; the cross-tile leaf order is handled by
    `tail_node_permutation` at exit. Each level doubles the width with
    the in-tile [all-left; all-right] concatenation; every width stays
    >= the entry tile (chosen >= 128 lanes by the caller), clear of
    Mosaic's narrow-lane edge cases. Correction planes stay [16, 8, KG]
    and are repeated per level exactly like `_level_kernel`.
    """
    state = state_ref[:]
    ctrl = ctrl_ref[:][0]  # [T]
    masks = masks_lr_ref[:]  # [2, 11, 16, 8, 1]
    cwp_all = cwp_ref[:]  # [r, 16, 8, kg]
    cwl_all = cwl_ref[:]  # [r, kg]
    cwr_all = cwr_ref[:]  # [r, kg]
    for i in range(r):
        w = state.shape[-1]
        sig = _sigma(state)
        left = _aes_fixed_planes(masks[0], sig) ^ sig
        right = _aes_fixed_planes(masks[1], sig) ^ sig
        state = jnp.concatenate([left, right], axis=-1)
        ctrl2 = jnp.concatenate([ctrl, ctrl])
        cwp = pltpu.repeat(cwp_all[i], 2 * w // kg, axis=2)  # [16, 8, 2w]
        state = state ^ (cwp & ctrl2[None, None, :])
        t_new = state[0, 0]
        state = _zero_lsb_plane(state)
        cwl = pltpu.repeat(cwl_all[i][None, :], w // kg, axis=1)[0]
        cwr = pltpu.repeat(cwr_all[i][None, :], w // kg, axis=1)[0]
        cw_dir = jnp.concatenate(
            [ctrl & cwl, ctrl & cwr]
        )
        ctrl = t_new ^ cw_dir
    # Leaf value hash (MMO with the value key) + value correction.
    sig = _sigma(state)
    values = _aes_fixed_planes(masks_v_ref[:], sig) ^ sig
    wf = values.shape[-1]
    vc = pltpu.repeat(vc_ref[:], wf // kg, axis=2)
    out_ref[:] = values ^ (vc & ctrl[None, None, :])
    # Final packed control bits (hierarchical callers apply arithmetic
    # value corrections outside, per leaf control bit).
    outc_ref[:] = ctrl[None, :]


def _head_kernel(
    state_ref,
    ctrl_ref,
    cwp_ref,
    cwl_ref,
    cwr_ref,
    masks_lr_ref,
    out_ref,
    outc_ref,
    *,
    kg: int,
    r: int,
):
    """Expand the whole (narrow) entry width through the FIRST `r`
    levels in one launch: one HBM read of the [16, 8, G0] entry planes,
    one HBM write of the [16, 8, G0 << r] result.

    The narrow early levels are pure overhead off-chip: at the headline
    config they cost ~6 ms of XLA launches (or worse, per-level kernel
    launches) for microseconds of gate work (expand_profile 2026-07-31,
    levels 0-8). A single tile covers the full width, so the per-level
    in-kernel [all-left; all-right] concatenation is exactly the global
    level order — no exit permutation, unlike the tiled tail."""
    state = state_ref[:]
    ctrl = ctrl_ref[:][0]
    masks = masks_lr_ref[:]
    cwp_all = cwp_ref[:]
    cwl_all = cwl_ref[:]
    cwr_all = cwr_ref[:]
    for i in range(r):
        w = state.shape[-1]
        sig = _sigma(state)
        left = _aes_fixed_planes(masks[0], sig) ^ sig
        right = _aes_fixed_planes(masks[1], sig) ^ sig
        state = jnp.concatenate([left, right], axis=-1)
        ctrl2 = jnp.concatenate([ctrl, ctrl])
        cwp = pltpu.repeat(cwp_all[i], 2 * w // kg, axis=2)
        state = state ^ (cwp & ctrl2[None, None, :])
        t_new = state[0, 0]
        state = _zero_lsb_plane(state)
        cwl = pltpu.repeat(cwl_all[i][None, :], w // kg, axis=1)[0]
        cwr = pltpu.repeat(cwr_all[i][None, :], w // kg, axis=1)[0]
        ctrl = t_new ^ jnp.concatenate([ctrl & cwl, ctrl & cwr])
    out_ref[:] = state
    outc_ref[:] = ctrl[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def expand_head_planes_pallas(
    state: jnp.ndarray,
    ctrl: jnp.ndarray,
    cwp_head: jnp.ndarray,
    cwl_head: jnp.ndarray,
    cwr_head: jnp.ndarray,
    interpret: bool = False,
) -> tuple:
    """Fused head: the FIRST `r` expansion levels in one grid-(1,)
    launch over the full (narrow) width.

    state: uint32[16, 8, G0] entry planes (G0 = key_groups at the top
    of the expansion); ctrl: uint32[G0]; cwp_head: uint32[r, 16, 8, KG]
    per-level seed-correction planes; cwl_head / cwr_head: uint32[r, KG]
    per-level packed direction bits. Returns
    (state uint32[16, 8, G0 << r], ctrl uint32[G0 << r]) bit-identical
    to `r` successive `expand_level_planes` applications — single-tile,
    so no exit permutation. The caller bounds G0 << r so the in-kernel
    working set stays within VMEM (~16 MB/core)."""
    _, _, g0 = state.shape
    r = cwp_head.shape[0]
    kg = cwp_head.shape[-1]
    if g0 % kg:
        raise ValueError(
            f"entry lanes {g0} must be a multiple of key groups {kg}"
        )
    gf = g0 << r
    out, outc = pl.pallas_call(
        functools.partial(_head_kernel, kg=kg, r=r),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((16, 8, g0), lambda l: (0, 0, 0)),
            pl.BlockSpec((1, g0), lambda l: (0, 0)),
            pl.BlockSpec((r, 16, 8, kg), lambda l: (0, 0, 0, 0)),
            pl.BlockSpec((r, kg), lambda l: (0, 0)),
            pl.BlockSpec((r, kg), lambda l: (0, 0)),
            pl.BlockSpec((2, 11, 16, 8, 1), lambda l: (0, 0, 0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((16, 8, gf), lambda l: (0, 0, 0)),
            pl.BlockSpec((1, gf), lambda l: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((16, 8, gf), U32),
            jax.ShapeDtypeStruct((1, gf), U32),
        ),
        interpret=interpret,
    )(
        state, ctrl[None, :], cwp_head, cwl_head, cwr_head, _MASKS_LR
    )
    return out, outc[0]


def tail_node_permutation(
    entry_order: np.ndarray, r: int, tile_nodes: int
) -> tuple[np.ndarray, np.ndarray]:
    """Leaf order of the tiled tail expansion.

    entry_order[pos] = natural node index (at the split level) sitting
    at plane position `pos` when the tail starts. Each tile of
    `tile_nodes` entry nodes expands independently with per-level
    [all-left; all-right] concatenation; tiles' outputs concatenate in
    tile order. Returns (order, perm): order[pos] = natural leaf index
    at final position pos, and perm = argsort(order), i.e. perm[g] = the
    final position of natural leaf g (the exit-gather index vector).
    """
    chunks = []
    for lo in range(0, len(entry_order), tile_nodes):
        m = np.asarray(entry_order[lo : lo + tile_nodes], dtype=np.int64)
        for _ in range(r):
            m = np.concatenate([2 * m, 2 * m + 1])
        chunks.append(m)
    order = np.concatenate(chunks) if chunks else np.zeros(0, np.int64)
    return order, np.argsort(order)


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_lanes")
)
def expand_tail_planes_pallas(
    state: jnp.ndarray,
    ctrl: jnp.ndarray,
    cwp_tail: jnp.ndarray,
    cwl_tail: jnp.ndarray,
    cwr_tail: jnp.ndarray,
    vc_kg: jnp.ndarray,
    tile_lanes: int,
    interpret: bool = False,
) -> tuple:
    """Fused tail: the last `r` expansion levels + the leaf value hash,
    one kernel launch per entry tile (grid-(1,) each; multi-step lane
    grids crash tpu_compile_helper on v5e).

    state: uint32[16, 8, G0] planes at the split level; ctrl: uint32[G0];
    cwp_tail: uint32[r, 16, 8, KG] per-level seed-correction planes;
    cwl_tail / cwr_tail: uint32[r, KG] per-level packed direction bits;
    vc_kg: uint32[16, 8, KG] value-correction planes. Returns
    (value planes uint32[16, 8, G0 * 2^r], packed leaf control bits
    uint32[G0 * 2^r]) in TILED order — compose `tail_node_permutation`
    at exit to recover natural block order.
    """
    _, _, g0 = state.shape
    r = cwp_tail.shape[0]
    kg = cwp_tail.shape[-1]
    _check_tile(tile_lanes, g0, kg)
    ctrl2 = ctrl[None, :]
    masks_v = jnp.asarray(_MASKS_VALUE)

    def call(state_c, ctrl_c):
        t = state_c.shape[-1]
        return pl.pallas_call(
            functools.partial(_tail_kernel, kg=kg, r=r),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((r, 16, 8, kg), lambda l: (0, 0, 0, 0)),
                pl.BlockSpec((r, kg), lambda l: (0, 0)),
                pl.BlockSpec((r, kg), lambda l: (0, 0)),
                pl.BlockSpec((16, 8, kg), lambda l: (0, 0, 0)),
                pl.BlockSpec(
                    (2, 11, 16, 8, 1), lambda l: (0, 0, 0, 0, 0)
                ),
                pl.BlockSpec((11, 16, 8, 1), lambda l: (0, 0, 0, 0)),
            ],
            out_specs=(
                pl.BlockSpec((16, 8, t << r), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t << r), lambda l: (0, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((16, 8, t << r), U32),
                jax.ShapeDtypeStruct((1, t << r), U32),
            ),
            interpret=interpret,
        )(
            state_c, ctrl_c, cwp_tail, cwl_tail, cwr_tail, vc_kg,
            _MASKS_LR, masks_v,
        )

    vs, cs = [], []
    for lo in range(0, g0, tile_lanes):
        v, c = call(state[:, :, lo : lo + tile_lanes],
                    ctrl2[:, lo : lo + tile_lanes])
        vs.append(v)
        cs.append(c[0])
    return jnp.concatenate(vs, axis=-1), jnp.concatenate(cs)


def _walk_kernel(
    state_ref,
    ctrl_ref,
    off_ref,
    cwp_ref,
    cwl_ref,
    cwr_ref,
    vc_ref,
    masks_lr_ref,
    masks_v_ref,
    out_ref,
    outc_ref,
    *,
    kg: int,
    r: int,
    value_hash: bool,
    unroll: bool = True,
    compact: bool = False,
):
    """Constant-width descent: `r` levels + optional leaf value hash at a
    FIXED lane width, using the per-lane select-key AES of `_path_kernel`
    instead of the twin left/right hashes of `_tail_kernel`/`_head_kernel`.

    Entry seeds arrive pre-replicated (every lane already holds the seed
    of its leaf's ancestor at the split level), so the per-level
    [all-left; all-right] lane concatenation — the doubling-width
    construct Mosaic rejects at serving shapes on the 2026-08-01 v5e
    toolchain — disappears: every intermediate is the same [16, 8, W]
    tile-aligned shape, and leaves exit in NATURAL order (no exit
    permutation). Each level hashes once with per-lane key select
    (`dpf/internal/aes_128_fixed_key_hash_hwy.h:123-155` semantics), so
    the gate work per level is HALF the twin-hash kernels'; the
    replication inflates total gate work by ~r/2 over perfect doubling,
    which is noise against the HBM traffic both designs already save.

    off_ref: uint32[1, W] leaf offset of each lane within its entry
    node's 2^r block (precomputed outside; bit r-1-i selects the key at
    level i — MSB first). Everything else matches `_tail_kernel`.

    With `compact` the entry arrives UNREPLICATED ([16, 8, W >> r]) and
    the kernel replicates it 2^r-fold with the whole-array repeat (the
    construct every serving kernel already uses for corrections) — the
    tile's lane order is then offset-major, `off` is supplied to match,
    and the full-width replicated array never touches HBM.
    """
    state = state_ref[:]
    ctrl = ctrl_ref[:][0]  # [W] (or [W >> r] compact) packed bits
    if compact:
        state = pltpu.repeat(state, 1 << r, axis=2)
        ctrl = pltpu.repeat(ctrl[None, :], 1 << r, axis=1)[0]
    off = off_ref[:]  # [1, W]
    masks = masks_lr_ref[:]  # [2, 11, 16, 8, 1]
    cwp_all = cwp_ref[:]  # [r, 16, 8, kg]
    cwl_all = cwl_ref[:]  # [r, kg]
    cwr_all = cwr_ref[:]  # [r, kg]
    w = state.shape[-1]
    reps = w // kg
    zero = jnp.uint32(0)

    def level(i, state, ctrl, cwp_i, cwl_i, cwr_i):
        bit = (off >> (jnp.uint32(r - 1) - i)) & jnp.uint32(1)  # [1, W]
        selw = zero - bit  # 0x0 / 0xFFFFFFFF per lane
        selb = selw[0][None, None, :]
        h = _aes_select_planes(masks, selb, _sigma(state))
        cwp = pltpu.repeat(cwp_i, reps, axis=2)  # [16, 8, W]
        h = h ^ (cwp & ctrl[None, None, :])
        t_new = h[0, 0]
        state = _zero_lsb_plane(h)
        cwl = pltpu.repeat(cwl_i[None, :], reps, axis=1)[0]
        cwr = pltpu.repeat(cwr_i[None, :], reps, axis=1)[0]
        cw_dir = (cwl & ~selw[0]) | (cwr & selw[0])
        return state, t_new ^ (ctrl & cw_dir)

    if unroll:
        for i in range(r):
            state, ctrl = level(
                jnp.uint32(i), state, ctrl,
                cwp_all[i], cwl_all[i], cwr_all[i],
            )
    else:
        # Constant width makes the level loop a real fori_loop: the
        # program holds ONE select-key AES body regardless of depth,
        # where the unrolled form at r=9..13 carries 10-14 of them —
        # exactly the program-size regime where Mosaic has rejected or
        # hung on the doubling kernels.
        def body(i, carry):
            state, ctrl = carry
            return level(
                i.astype(jnp.uint32), state, ctrl,
                cwp_all[i], cwl_all[i], cwr_all[i],
            )

        state, ctrl = jax.lax.fori_loop(0, r, body, (state, ctrl))
    if value_hash:
        sig = _sigma(state)
        values = _aes_fixed_planes(masks_v_ref[:], sig) ^ sig
        vc = pltpu.repeat(vc_ref[:], reps, axis=2)
        out_ref[:] = values ^ (vc & ctrl[None, None, :])
    else:
        out_ref[:] = state
    outc_ref[:] = ctrl[None, :]


def replicate_entry_planes(
    state: jnp.ndarray, ctrl: jnp.ndarray, kg: int, times: int
) -> tuple:
    """[16, 8, n*kg] entry planes -> [16, 8, n*times*kg] with each
    node's kg-lane block repeated `times` consecutively (and likewise
    for the packed control words), so lane (node*times + j)*kg + kw
    holds node's seed for every j — the wide-walk entry layout."""
    p, q, g = state.shape
    n = g // kg
    state_r = jnp.broadcast_to(
        state.reshape(p, q, n, 1, kg), (p, q, n, times, kg)
    ).reshape(p, q, n * times * kg)
    ctrl_r = jnp.broadcast_to(
        ctrl.reshape(n, 1, kg), (n, times, kg)
    ).reshape(n * times * kg)
    return state_r, ctrl_r


@functools.partial(
    jax.jit,
    static_argnames=(
        "r", "tile_lanes", "value_hash", "node_lanes", "unroll",
        "compact_entry", "interpret",
    ),
)
def walk_descend_planes_pallas(
    state: jnp.ndarray,
    ctrl: jnp.ndarray,
    cwp_all: jnp.ndarray,
    cwl_all: jnp.ndarray,
    cwr_all: jnp.ndarray,
    vc_kg: jnp.ndarray | None = None,
    *,
    r: int,
    tile_lanes: int | None = None,
    value_hash: bool = False,
    node_lanes: int | None = None,
    unroll: bool = True,
    compact_entry: bool = False,
    interpret: bool = False,
) -> tuple:
    """Fixed-width fused descent of the last (or first) `r` expansion
    levels, optionally ending in the leaf value hash.

    state: uint32[16, 8, G0] planes at the split level; ctrl:
    uint32[G0]; cwp_all: uint32[r, 16, 8, KG]; cwl_all / cwr_all:
    uint32[r, KG]; vc_kg (with value_hash): uint32[16, 8, KG]. Returns
    (out uint32[16, 8, G0 << r], ctrl uint32[G0 << r]) in NATURAL leaf
    order (leaf g = entry_node * 2^r + offset) — no exit permutation.
    `node_lanes` is the lane width of one tree node's block (defaults
    to KG — the dense-serving layout where a node spans the per-key
    correction words; the hierarchical single-key layout packs 32
    prefixes per word instead, with KG=1 shared corrections, so a node
    spans prefix_words lanes there).

    Default mode replicates the entry 2^r-fold outside the kernel, then
    each `tile_lanes` output tile descends independently at constant
    width; the replication materializes full-width in HBM (one extra
    write+read of W lanes ~= the kernel's own output traffic — ~40 us
    at the q128 serving width, but ~0.7 ms at the ld24 hierarchical
    width). `compact_entry=True` removes it: each tile reads only its
    UNREPLICATED entry chunk ([16, 8, tile >> r]) and the kernel
    replicates in VMEM with the whole-array repeat; the tile's lanes
    are then offset-major and the RETURN IS NOT NATURAL ORDER — callers
    compose `walk_compact_leaf_order` into their exit gather. Requires
    tile % (node_lanes << r) == 0. Reference semantics: `ExpandSeeds` +
    `HashExpandedSeeds`
    (`dpf/distributed_point_function.cc:289-372,523-547`), evaluated as
    a per-leaf path walk (`dpf/internal/evaluate_prg_hwy.cc:150-539`).
    """
    _, _, g0 = state.shape
    kg = cwp_all.shape[-1]
    if node_lanes is None:
        node_lanes = kg
    if g0 % node_lanes or node_lanes % kg:
        raise ValueError(
            f"entry lanes {g0} must be a multiple of node lanes "
            f"{node_lanes}, which must be a multiple of key groups {kg}"
        )
    if value_hash and vc_kg is None:
        raise ValueError(
            "value_hash=True requires vc_kg (a zero correction would "
            "silently break share reconstruction)"
        )
    w = g0 << r
    if tile_lanes is None:
        tile = pick_walk_tile(w, kg, node_lanes, compact_entry, r)
    else:
        tile = tile_lanes
    _check_tile(tile, w, kg)
    if compact_entry:
        if tile % (node_lanes << r) or w % tile:
            raise ValueError(
                f"compact_entry requires tile {tile} to cover whole "
                f"node blocks: multiple of node_lanes<<r "
                f"({node_lanes << r}) dividing {w}"
            )
        # Offset-major within each tile, matching the in-kernel
        # whole-array repeat: lane = off * entry_chunk + entry_lane.
        e = tile >> r
        off_np = np.repeat(np.arange(1 << r, dtype=np.uint32), e)
        state_r, ctrl_r = state, ctrl
    else:
        state_r, ctrl_r = replicate_entry_planes(
            state, ctrl, node_lanes, 1 << r
        )
        # Leaf offset of each lane within its entry node's 2^r block.
        off_np = np.tile(
            np.repeat(np.arange(1 << r, dtype=np.uint32), node_lanes),
            g0 // node_lanes,
        )
    off = jnp.asarray(off_np[None, :])
    if vc_kg is None:
        vc_kg = jnp.zeros((16, 8, kg), U32)
    masks_v = jnp.asarray(_MASKS_VALUE)
    ctrl2 = ctrl_r[None, :]

    def call(state_c, ctrl_c, off_c):
        t = off_c.shape[-1]
        te = state_c.shape[-1]  # == t >> r when compact, else t
        return pl.pallas_call(
            functools.partial(
                _walk_kernel, kg=kg, r=r, value_hash=value_hash,
                unroll=unroll, compact=compact_entry,
            ),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((16, 8, te), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, te), lambda l: (0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((r, 16, 8, kg), lambda l: (0, 0, 0, 0)),
                pl.BlockSpec((r, kg), lambda l: (0, 0)),
                pl.BlockSpec((r, kg), lambda l: (0, 0)),
                pl.BlockSpec((16, 8, kg), lambda l: (0, 0, 0)),
                pl.BlockSpec(
                    (2, 11, 16, 8, 1), lambda l: (0, 0, 0, 0, 0)
                ),
                pl.BlockSpec((11, 16, 8, 1), lambda l: (0, 0, 0, 0)),
            ],
            out_specs=(
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((16, 8, t), U32),
                jax.ShapeDtypeStruct((1, t), U32),
            ),
            interpret=interpret,
        )(
            state_c, ctrl_c, off_c, cwp_all, cwl_all, cwr_all, vc_kg,
            _MASKS_LR, masks_v,
        )

    outs, cs = [], []
    for lo in range(0, w, tile):
        if compact_entry:
            e = tile >> r
            lo_e = lo >> r
            o, c = call(
                state_r[:, :, lo_e : lo_e + e],
                ctrl2[:, lo_e : lo_e + e],
                off,
            )
        else:
            o, c = call(
                state_r[:, :, lo : lo + tile],
                ctrl2[:, lo : lo + tile],
                off[:, lo : lo + tile],
            )
        outs.append(o)
        cs.append(c[0])
    return jnp.concatenate(outs, axis=-1), jnp.concatenate(cs)


def walk_compact_leaf_order(
    entry_order: np.ndarray, r: int, nodes_per_tile: int
) -> np.ndarray:
    """Leaf order after a compact-entry walk-descent: each tile of
    `nodes_per_tile` entry nodes exits offset-major (all nodes' offset
    0, then offset 1, ...), tiles concatenating in entry order:
    order[t * npt * 2^r + off * npt + p] =
    entry_order[t * npt + p] * 2^r + off."""
    npt = nodes_per_tile
    m = np.asarray(entry_order, dtype=np.int64)
    chunks = []
    for lo in range(0, len(m), npt):
        blk = m[lo : lo + npt]
        chunks.append(
            (blk[None, :] * (1 << r)
             + np.arange(1 << r, dtype=np.int64)[:, None]).reshape(-1)
        )
    return np.concatenate(chunks)


def _path_kernel(
    state_ref,
    ctrl_ref,
    sel_ref,
    cwp_ref,
    cwl_ref,
    cwr_ref,
    masks_ref,
    outs_ref,
    outc_ref,
    *,
    reps: int,
    per_seed: bool,
):
    """One path-walk level: select-key AES (per-lane left/right round
    keys from the packed path-bit mask — the reference's per-lane key
    select, `dpf/internal/aes_128_fixed_key_hash_hwy.h:123-155`), seed
    correction, LSB extract/clear, and the direction-corrected control
    update, fused in VMEM. With `per_seed` the correction refs are
    lane-aligned (batch-of-keys mode, `evaluate_and_apply`); otherwise
    they are [.., KG] per-key words tiled in-kernel."""
    sig = _sigma(state_ref[:])
    masks = masks_ref[:]  # [2, 11, 16, 8, 1] left/right plane masks
    sel = sel_ref[:]  # [1, T] packed path bits
    selb = sel[0][None, None, :]
    h = _aes_select_planes(masks, selb, sig)

    ctrl = ctrl_ref[:]  # [1, T]
    if per_seed:
        cwp = cwp_ref[:]
        cwl = cwl_ref[:]
        cwr = cwr_ref[:]
    else:
        cwp = pltpu.repeat(cwp_ref[:], reps, axis=2)
        cwl = pltpu.repeat(cwl_ref[:], reps, axis=1)
        cwr = pltpu.repeat(cwr_ref[:], reps, axis=1)
    h = h ^ (cwp & ctrl[0][None, None, :])
    t_new = h[0, 0]
    outs_ref[:] = _zero_lsb_plane(h)
    cw_dir = (sel[0] & cwr[0]) | (~sel[0] & cwl[0])
    outc_ref[:] = (t_new ^ (ctrl[0] & cw_dir))[None, :]


@functools.partial(
    jax.jit, static_argnames=("per_seed", "interpret", "tile_lanes")
)
def path_level_planes_pallas(
    state: jnp.ndarray,
    ctrl: jnp.ndarray,
    sel: jnp.ndarray,
    cwp: jnp.ndarray,
    cwl: jnp.ndarray,
    cwr: jnp.ndarray,
    per_seed: bool,
    interpret: bool = False,
    tile_lanes: int | None = None,
):
    """One path-walk level on [16, 8, G] planes.

    sel: uint32[G] packed path bits (1 -> right key). With per_seed,
    cwp is uint32[16, 8, G] lane-aligned correction planes and cwl/cwr
    are uint32[G]; otherwise cwp is [16, 8, KG] / cwl, cwr [KG] per-key
    words tiled across lanes in-kernel. Returns (state [16, 8, G],
    ctrl [G]) — the fused body of `dpf._eval_paths_planes`."""
    _, _, g = state.shape
    kg = g if per_seed else cwp.shape[-1]
    if tile_lanes is None:
        tile = _pick_tile(g, kg if not per_seed else 1)
    else:
        tile = tile_lanes
    _check_tile(tile, g, 1 if per_seed else kg)
    ctrl2 = ctrl[None, :]
    sel2 = sel[None, :]
    cwl2 = cwl[None, :]
    cwr2 = cwr[None, :]

    def call(state_c, ctrl_c, sel_c, cwp_c, cwl_c, cwr_c):
        # Grid-(1,) per lane chunk (multi-step lane grids crash
        # tpu_compile_helper on v5e — see `expand_level_planes_pallas`).
        t = state_c.shape[-1]
        # A chunk can be narrower than the nominal tile.
        reps = t // kg if not per_seed else 1
        if per_seed:
            cw_specs = [
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
            ]
        else:
            cw_specs = [
                pl.BlockSpec((16, 8, kg), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, kg), lambda l: (0, 0)),
                pl.BlockSpec((1, kg), lambda l: (0, 0)),
            ]
        return pl.pallas_call(
            functools.partial(_path_kernel, reps=reps, per_seed=per_seed),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
                *cw_specs,
                pl.BlockSpec(
                    (2, 11, 16, 8, 1), lambda l: (0, 0, 0, 0, 0)
                ),
            ],
            out_specs=(
                pl.BlockSpec((16, 8, t), lambda l: (0, 0, 0)),
                pl.BlockSpec((1, t), lambda l: (0, 0)),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((16, 8, t), U32),
                jax.ShapeDtypeStruct((1, t), U32),
            ),
            interpret=interpret,
        )(state_c, ctrl_c, sel_c, cwp_c, cwl_c, cwr_c, _MASKS_LR)

    ss, cs = [], []
    for lo in range(0, g, tile):
        sl = slice(lo, lo + tile)
        outs, outc = call(
            state[:, :, sl],
            ctrl2[:, sl],
            sel2[:, sl],
            cwp[:, :, sl] if per_seed else cwp,
            cwl2[:, sl] if per_seed else cwl2,
            cwr2[:, sl] if per_seed else cwr2,
        )
        ss.append(outs)
        cs.append(outc[0])
    return jnp.concatenate(ss, axis=-1), jnp.concatenate(cs)
