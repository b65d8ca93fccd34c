"""Plane-resident dense-PIR expansion: the whole subtree walk stays in
bitsliced plane layout.

`dense_eval.evaluate_selection_blocks` re-enters the bitsliced AES kernel
per level, paying a 32x32 bit-transpose into plane layout and another one
back out for every level's hash, plus per-level `repeat`/select-mask
round-key composition. But every *other* operation of the DPF expansion
recurrence is linear over GF(2):

* seed correction is an XOR under a control mask,
* sigma is a byte-axis rewiring (`aes_bitslice.sigma_planes`),
* the control bit is bit-plane (0, 0); clearing the seed LSB zeroes it,
* child doubling becomes concatenation when children are ordered
  [all-left; all-right] instead of interleaved.

So the expansion can stay in plane layout end to end: transpose the nk
subtree roots in once, run `expand_levels` levels of two fixed-key
plane-space hashes (left/right children of every node — same AES work as
the one-pass key-selected hash, with no per-level transposes, no
`repeat`, and plain all-ones round-key planes), hash the leaves with the
value key, and transpose out once.

The price is leaf order: appending [all-left; all-right] per level makes
the final node order the **bit-reversal** of the natural block index
(position of leaf with path bits b1..be is be..b1). The serving path
compensates for free by bit-reversal-permuting the database's record
*blocks* once at staging (`bitrev_permutation`); the drop-in wrapper
`evaluate_selection_blocks_planes` instead gathers leaves back to natural
order for bit-identity with `evaluate_selection_blocks`.

Lane layout: flattened node-major/key-minor (lane = node * nk + key) with
nk padded to a multiple of 32, so each packed uint32 word holds 32 keys
of one node and per-key correction words broadcast to [G] words by a
plain `tile` (`pack_key_planes` / `pack_key_bits`).

Reference semantics: `ExpandSeeds`
(`dpf/distributed_point_function.cc:289-372`) restricted to the covering
subtree, as in `dense_eval.py`.
"""

from __future__ import annotations

import functools
import os

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
from ..ops.expand_planes_pallas import (
    expand_head_planes_pallas,
    expand_level_planes_pallas,
    expand_tail_planes_pallas,
    tail_node_permutation,
    value_hash_planes_pallas,
    walk_descend_planes_pallas,
)
from .dense_eval import _walk_zeros
from ..utils.profiling import scoped

U32 = jnp.uint32


def bitrev_permutation(levels: int) -> np.ndarray:
    """perm[g] = bit-reversal of g over `levels` bits (an involution)."""
    n = 1 << levels
    perm = np.zeros(n, dtype=np.int64)
    for g in range(n):
        r = 0
        x = g
        for _ in range(levels):
            r = (r << 1) | (x & 1)
            x >>= 1
        perm[g] = r
    return perm


def walk_leaf_order(entry_order: np.ndarray, r: int) -> np.ndarray:
    """Leaf order after a fixed-width walk-descent of `r` levels: each
    entry node's 2^r leaves exit consecutively in natural offset order
    (`walk_descend_planes_pallas`), so order[p * 2^r + off] =
    entry_order[p] * 2^r + off."""
    m = np.asarray(entry_order, dtype=np.int64)
    return (
        m[:, None] * (1 << r) + np.arange(1 << r, dtype=np.int64)[None, :]
    ).reshape(-1)


def _walk_compact_enabled() -> bool:
    """DPF_TPU_WALK_COMPACT=1 routes the walk kernels through the
    compact-entry mode (in-kernel replication, no full-width HBM
    staging of the replicated entry). Default off until the mode is
    hardware-proven; read at dispatch time like the other knobs."""
    return os.environ.get("DPF_TPU_WALK_COMPACT", "") == "1"


def _walk_phase(state, ctrl, cwp, cwl, cwr, vc, *, r, node_lanes,
                leaf_order, compact, value_hash=False):
    """One walk-descent phase (head or tail) plus its leaf-order
    composition. `compact` arrives as a trace-time-static flag (the
    dispatcher reads the env knob); walk_plan is the single source of
    truth for the tile/mode pair. Returns ((state, ctrl),
    new_leaf_order)."""
    from ..ops.expand_planes_pallas import (
        compose_walk_leaf_order,
        walk_plan,
    )

    kg = cwp.shape[-1]
    w = state.shape[-1] << r
    tile, compact, npt = walk_plan(w, kg, node_lanes, r, compact)
    out = walk_descend_planes_pallas(
        state, ctrl, cwp, cwl, cwr, vc,
        r=r, tile_lanes=tile, value_hash=value_hash,
        node_lanes=node_lanes, compact_entry=compact,
    )
    return out, compose_walk_leaf_order(leaf_order, r, compact, npt)


def pack_key_planes(cw: jnp.ndarray) -> jnp.ndarray:
    """uint32[nk, 4] per-key 128-bit words -> uint32[16, 8, nk/32] planes
    packed over the key axis (word m bit i = key 32m+i's bit).

    Plane (byte j, bit i) of limb l bit b sits at flat index 32l + b —
    the limb-little-endian bit order (`aes_bitslice.limbs_to_planes`).
    """
    nk = cw.shape[0]
    if nk % 32:
        raise ValueError("key count must be padded to a multiple of 32")
    shifts = jnp.arange(32, dtype=U32)
    bits = (cw[:, :, None] >> shifts) & U32(1)  # [nk, 4, 32]
    bits = bits.reshape(nk // 32, 32, 128)
    words = (bits << shifts[None, :, None]).sum(axis=1, dtype=U32)
    return jnp.moveaxis(words, 0, -1).reshape(16, 8, -1)


def pack_key_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32[nk] 0/1 -> uint32[nk/32] packed (word m bit i = key 32m+i).

    Same packing as `aes_bitslice.pack_select_bits` (the single
    implementation), with the key-count contract checked."""
    if bits.shape[0] % 32:
        raise ValueError("key count must be padded to a multiple of 32")
    from ..ops.aes_bitslice import pack_select_bits

    return pack_select_bits(bits)


def _tile_keys(words: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Broadcast per-key packed words over the node axis: [..., nk/32] ->
    [..., num_groups] (node-major lanes: group g covers keys of node
    g // (nk/32))."""
    reps = num_groups // words.shape[-1]
    return jnp.tile(words, (1,) * (words.ndim - 1) + (reps,))


def expand_level_planes(state, ctrl, cw_p, cwl_w, cwr_w):
    """One [all-left; all-right] plane-space expansion level — the shared
    recurrence body of this module's covering-subtree expansion and
    `dpf._expand_levels_planes_fn`.

    state: [16, 8, G] planes; ctrl: uint32[G] packed parent control bits;
    cw_p: [16, 8, 2G or 1] seed-correction planes for the doubled width;
    cwl_w / cwr_w: packed direction-correction words broadcastable to [G]
    (one half each). Returns (state [16, 8, 2G], ctrl [2G])."""
    sig = sigma_planes(state)
    left = aes_rounds_planes(fixed_keys.RK_LEFT, sig) ^ sig
    right = aes_rounds_planes(fixed_keys.RK_RIGHT, sig) ^ sig
    state = jnp.concatenate([left, right], axis=-1)
    ctrl2 = jnp.concatenate([ctrl, ctrl])  # parent bit, both halves
    state = state ^ (cw_p & ctrl2[None, None, :])
    t_new = state[0, 0]  # LSB plane = control bits
    state = state.at[0, 0].set(jnp.zeros_like(t_new))
    half = ctrl.shape[0]
    cw_dir = jnp.concatenate(
        [
            jnp.broadcast_to(cwl_w, (half,)),
            jnp.broadcast_to(cwr_w, (half,)),
        ]
    )
    return state, t_new ^ (ctrl2 & cw_dir)


def evaluate_selection_blocks_planes(
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
    force_planes: bool = False,
) -> jnp.ndarray:
    """Plane-resident expansion with a padding-ratio guard.

    The key axis is padded to a multiple of 32 and the dead lanes double
    along with the live ones at every level, so a batch of e.g. 3 queries
    would pay ~10x the AES work. When the padding overhead exceeds 25%,
    such batches are served by the limb kernel (which pads per 32-block
    hash call and reaches full occupancy once the width fills a word).
    `force_planes=True` bypasses the guard (differential tests)."""
    nk = seeds0.shape[0]
    padded = ((nk + 31) // 32) * 32
    if not force_planes and not bitrev_leaves and padded * 4 > nk * 5:
        from .dense_eval import evaluate_selection_blocks

        return evaluate_selection_blocks(
            seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            walk_levels=walk_levels,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
        )
    mode = _level_kernel_enabled()
    tail_levels = tile_nodes = head_levels = 0
    tail_kind = head_kind = "concat"
    if mode:
        # Walk mode runs the fixed-width descent kernels (head + tail);
        # tail mode fuses the last levels + value hash per subtree tile
        # (one kernel launch each); the fused head covers the narrow
        # entry levels in one launch; the per-level kernels (if any
        # levels remain) cover the middle. A kernel that fails raises.
        kg = padded // 32
        if mode == "walk" and not bitrev_leaves:
            # The walk kernels exit in natural leaf order, which the
            # exit gather absorbs; the bitrev-staged serving path
            # (bitrev_leaves=True) assumes doubling order, so walk
            # stays off there until staging is order-aware.
            tail_kind = head_kind = "walk"
            tail_levels = min(_tail_levels_requested(), expand_levels)
            head_levels = _head_split(
                kg, expand_levels - tail_levels, walk=True
            )
        else:
            if mode == "tail" and not bitrev_leaves:
                tail_levels, tile_nodes = _tail_split(kg, expand_levels)
            head_levels = _head_split(kg, expand_levels - tail_levels)
    return _evaluate_selection_blocks_planes_jit(
        seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
        walk_levels=walk_levels,
        expand_levels=expand_levels,
        num_blocks=num_blocks,
        bitrev_leaves=bitrev_leaves,
        level_kernel=bool(mode),
        tail_levels=tail_levels,
        tail_tile_nodes=tile_nodes,
        head_levels=head_levels,
        tail_kind=tail_kind,
        head_kind=head_kind,
        walk_compact=tail_kind == "walk" and _walk_compact_enabled(),
    )



def _trace_state_clean() -> bool:
    """True when no jax trace is active (private API, so fail open: a
    missing symbol just means the self-check runs as before)."""
    try:
        from jax._src import core as _core

        return bool(_core.trace_state_clean())
    except Exception:  # noqa: BLE001 - jax internals moved
        return True


_LEVEL_KERNEL_VERIFIED = False


def _level_kernel_selfcheck() -> bool:
    """One-time on-device bit-identity check of the fused level kernels
    against their XLA twins on a small random instance (the serving-path
    analog of bench.py's inner-product verification): auto mode must
    never serve a kernel Mosaic compiles incorrectly."""
    global _LEVEL_KERNEL_VERIFIED
    if _LEVEL_KERNEL_VERIFIED:
        return True
    import numpy as _np

    from ..ops.expand_planes_pallas import (
        expand_level_planes_pallas,
        value_hash_planes_pallas,
    )

    rng = _np.random.default_rng(1234)
    g, nk = 64, 64
    state = jnp.asarray(
        rng.integers(0, 1 << 32, (16, 8, g), dtype=_np.uint32)
    )
    ctrl = jnp.asarray(rng.integers(0, 1 << 32, (g,), dtype=_np.uint32))
    cwp = pack_key_planes(
        jnp.asarray(rng.integers(0, 1 << 32, (nk, 4), dtype=_np.uint32))
    )
    cwl = pack_key_bits(
        jnp.asarray(rng.integers(0, 2, (nk,), dtype=_np.uint32))
    )
    cwr = pack_key_bits(
        jnp.asarray(rng.integers(0, 2, (nk,), dtype=_np.uint32))
    )
    want_s, want_c = expand_level_planes(
        state, ctrl, _tile_keys(cwp, 2 * g), _tile_keys(cwl, g),
        _tile_keys(cwr, g),
    )
    got_s, got_c = expand_level_planes_pallas(state, ctrl, cwp, cwl, cwr)
    if not (
        _np.array_equal(_np.asarray(got_s), _np.asarray(want_s))
        and _np.array_equal(_np.asarray(got_c), _np.asarray(want_c))
    ):
        raise RuntimeError("level kernel/XLA bit mismatch on this device")
    # Chunked path (serving widths over _TILE_LANES run one grid-(1,)
    # call per lane slice): force sub-width tiles so the multi-call
    # assembly and its [all-left; all-right] order are checked on device.
    got_s, got_c = expand_level_planes_pallas(
        state, ctrl, cwp, cwl, cwr, tile_lanes=16
    )
    if not (
        _np.array_equal(_np.asarray(got_s), _np.asarray(want_s))
        and _np.array_equal(_np.asarray(got_c), _np.asarray(want_c))
    ):
        raise RuntimeError(
            "chunked level kernel/XLA bit mismatch on this device"
        )
    want_v = mmo_hash_planes(fixed_keys.RK_VALUE, state) ^ (
        _tile_keys(cwp, g) & ctrl[None, None, :]
    )
    got_v = value_hash_planes_pallas(state, ctrl, cwp)
    if not _np.array_equal(_np.asarray(got_v), _np.asarray(want_v)):
        raise RuntimeError("value kernel/XLA bit mismatch on this device")

    from ..ops.aes_bitslice import aes_rounds_select_planes
    from ..ops.expand_planes_pallas import path_level_planes_pallas

    sel = jnp.asarray(rng.integers(0, 1 << 32, (g,), dtype=_np.uint32))
    sig = sigma_planes(state)
    h = aes_rounds_select_planes(
        fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, sel, sig
    ) ^ sig
    h = h ^ (_tile_keys(cwp, g) & ctrl[None, None, :])
    t_new = h[0, 0]
    want_ps = h.at[0, 0].set(jnp.zeros_like(t_new))
    cw_dir = (sel & _tile_keys(cwr, g)) | (~sel & _tile_keys(cwl, g))
    want_pc = t_new ^ (ctrl & cw_dir)
    got_ps, got_pc = path_level_planes_pallas(
        state, ctrl, sel, cwp, cwl, cwr, per_seed=False
    )
    if not (
        _np.array_equal(_np.asarray(got_ps), _np.asarray(want_ps))
        and _np.array_equal(_np.asarray(got_pc), _np.asarray(want_pc))
    ):
        raise RuntimeError("path kernel/XLA bit mismatch on this device")
    _LEVEL_KERNEL_VERIFIED = True
    return True


def _head_max_lanes() -> int:
    """Exit-width cap for the fused head kernel (DPF_TPU_HEAD_MAX_LANES,
    default 2048: the in-kernel working set is ~6 copies of the widest
    [16, 8, W] u32 state, ~3 MB at 2048 lanes — comfortably inside the
    ~16 MB/core VMEM)."""
    try:
        return max(0, int(os.environ.get("DPF_TPU_HEAD_MAX_LANES", "2048")))
    except ValueError:
        return 2048


def _auto_head_count(cap: int, entry_lanes: int, avail: int) -> int:
    """Pure auto-sizing rule for the fused head, shared by the serving
    path (`_head_split`) and the hierarchical dispatch (`dpf.py`): fill
    levels until the exit width reaches `cap` lanes; a 1-level head is
    just a worse per-level launch, so the minimum is 2."""
    if avail <= 0 or entry_lanes <= 0 or cap < 2 * entry_lanes:
        return 0
    head = min(avail, (cap // entry_lanes).bit_length() - 1)
    return head if head >= 2 else 0


def _head_split(key_groups: int, a_levels: int, walk: bool = False) -> int:
    """How many entry levels the fused head kernel covers (0 = no head).

    DPF_TPU_HEAD_LEVELS forces the count (0 disables). Otherwise only
    walk mode has a head — its walk-kernel head runs from `key_groups`
    lanes until its exit width reaches the VMEM lane cap (a 1-level
    head is just a worse per-level launch, so the minimum is 2); the
    concat head is not in the TPU auto set (`TPU_AUTO_LEVEL_KERNEL`)."""
    if a_levels <= 0:
        return 0
    raw = os.environ.get("DPF_TPU_HEAD_LEVELS", "auto")
    if raw != "auto":
        try:
            return max(0, min(int(raw), a_levels))
        except ValueError:
            pass
    if not walk:
        return 0
    return _auto_head_count(_head_max_lanes(), key_groups, a_levels)


def _tail_levels_requested() -> int:
    """How many final levels the fused tail kernel should cover
    (DPF_TPU_TAIL_LEVELS, default 4: the measured hot levels are the
    last ~4 plus the value hash — expand_profile 2026-07-31)."""
    try:
        return max(1, int(os.environ.get("DPF_TPU_TAIL_LEVELS", "4")))
    except ValueError:
        return 4


def _tail_tile_target() -> int:
    """Target entry-tile lane count — the one place the
    DPF_TPU_TAIL_TILE_LANES knob is parsed."""
    try:
        target = int(os.environ.get("DPF_TPU_TAIL_TILE_LANES", "128"))
    except ValueError:
        target = 128
    return target


def _tail_split(
    key_groups: int,
    expand_levels: int,
    requested_levels: int | None = None,
    target_lanes: int | None = None,
) -> tuple:
    """(tail_levels, tile_nodes) for the fused tail: shrink the tail
    until the entry tile reaches the width floor — min(128 lanes, the
    explicit DPF_TPU_TAIL_TILE_LANES target, what the key-group packing
    can express, the whole tree) — so default-config in-kernel widths
    stay clear of the narrow-lane Mosaic regime while small probe/test
    tiles remain honored. Env knobs are read here, OUTSIDE the jit, and
    passed as static args — changing them between calls with identical
    shapes must not be silently ignored."""
    if requested_levels is None:
        requested_levels = _tail_levels_requested()
    if target_lanes is None:
        target_lanes = _tail_tile_target()
    best = 1 << (max(1, target_lanes // key_groups).bit_length() - 1)
    tail = min(requested_levels, expand_levels)
    if tail <= 0:
        return 0, 0
    floor = min(
        128, target_lanes, best * key_groups,
        key_groups << expand_levels,
    )
    def tile_nodes(a_levels):
        return min(best, 1 << a_levels)

    while (
        tail > 1
        and tile_nodes(expand_levels - tail) * key_groups < floor
    ):
        tail -= 1
    return tail, tile_nodes(expand_levels - tail)


# The level-kernel set the TPU auto path serves, fixed from what the v5e
# compiler accepts at the serving shape (tests/test_chip_compile.py):
# the per-level expansion and value-hash kernels. The fused concat head
# and tail are refused there ("Input offsets outside of the first
# tile"), and the walk-descent program did not finish compiling in 25
# minutes, so both stay behind DPF_TPU_LEVEL_KERNEL.
TPU_AUTO_LEVEL_KERNEL = "pallas"


def _level_kernel_enabled():
    """Whether (and how) the fused Pallas kernels serve the expansion:
    False (XLA levels), "pallas" (per-level kernels), "tail" (per-level
    kernels plus the fused multi-level tail + value hash), or "walk"
    (fixed-width walk-descent head + tail).

    DPF_TPU_LEVEL_KERNEL=pallas|tail|walk forces the mode, =xla disables
    it; auto serves `TPU_AUTO_LEVEL_KERNEL` on TPU and the XLA levels
    elsewhere. Outside a trace, auto mode bit-checks its kernels once
    (`_level_kernel_selfcheck`), and a mismatch raises."""
    mode = os.environ.get("DPF_TPU_LEVEL_KERNEL", "auto")
    if mode in ("pallas", "tail", "walk"):
        return mode
    if mode == "xla" or jax.default_backend() != "tpu":
        return False
    if not _LEVEL_KERNEL_VERIFIED and _trace_state_clean():
        _level_kernel_selfcheck()
    return TPU_AUTO_LEVEL_KERNEL


def warm_level_kernels():
    """Run the auto mode's one-time kernel self-check from eager
    context (and return the serving mode). Callers that trace the
    expansion into a bigger program (the sharded mesh step, the
    hierarchical evaluator) call this first: inside a trace the check
    cannot run."""
    return _level_kernel_enabled()


def level_kernel_status() -> dict:
    """The serving mode knob, the mode it resolves to, and whether the
    auto mode's self-check has passed in this process."""
    return {
        "mode": os.environ.get("DPF_TPU_LEVEL_KERNEL", "auto"),
        "serving": _level_kernel_enabled() or "xla",
        "verified": _LEVEL_KERNEL_VERIFIED,
    }



@functools.partial(
    jax.jit,
    static_argnames=(
        "walk_levels", "expand_levels", "num_blocks", "bitrev_leaves",
        "level_kernel", "tail_levels", "tail_tile_nodes", "head_levels",
        "tail_kind", "head_kind", "walk_compact",
    ),
)
@scoped("pir.expand")
def _evaluate_selection_blocks_planes_jit(
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
    level_kernel: bool = False,
    tail_levels: int = 0,
    tail_tile_nodes: int = 0,
    head_levels: int = 0,
    tail_kind: str = "concat",
    head_kind: str = "concat",
    walk_compact: bool = False,
) -> jnp.ndarray:
    """Drop-in for `dense_eval.evaluate_selection_blocks` (bit-identical
    output), computed with the plane-resident expansion.

    With `bitrev_leaves=True` the leaf axis stays in plane order (natural
    block g at position bitrev(g)) and is NOT truncated to `num_blocks` —
    for serving paths that bit-reverse the database instead.
    """
    nk = seeds0.shape[0]
    pad_keys = (-nk) % 32
    if pad_keys:
        seeds0 = jnp.pad(seeds0, ((0, pad_keys), (0, 0)))
        control0 = jnp.pad(control0, ((0, pad_keys),))
        cw_seeds = jnp.pad(cw_seeds, ((0, 0), (0, pad_keys), (0, 0)))
        cw_left = jnp.pad(cw_left, ((0, 0), (0, pad_keys)))
        cw_right = jnp.pad(cw_right, ((0, 0), (0, pad_keys)))
        last_vc = jnp.pad(last_vc, ((0, pad_keys), (0, 0)))
    nkp = nk + pad_keys
    key_groups = nkp // 32

    # Phase 1 (limb space, [nk, 4] only): walk the all-zeros prefix.
    seeds, control = _walk_zeros(
        seeds0, control0, cw_seeds[:walk_levels], cw_left[:walk_levels]
    )

    # Enter plane space once.
    state = limbs_to_planes(seeds)  # [16, 8, key_groups]
    ctrl = pack_key_bits(control.astype(U32))  # [key_groups]

    a_levels = expand_levels - tail_levels
    # Leaf order bookkeeping (static numpy): each phase appends its own
    # node order; the exit gather is argsort of the composition.
    leaf_order = np.zeros(1, dtype=np.int64)
    start = 0
    if head_levels:
        # Fused head: the first levels in ONE launch over the (narrow)
        # full width. The concat head is bit-identical to the per-level
        # sequence (doubling order); the walk head exits in natural
        # order, which the exit gather absorbs.
        hs = walk_levels
        cwp_head = jnp.stack(
            [pack_key_planes(cw_seeds[hs + j])
             for j in range(head_levels)]
        )
        cwl_head = jnp.stack(
            [pack_key_bits(cw_left[hs + j])
             for j in range(head_levels)]
        )
        cwr_head = jnp.stack(
            [pack_key_bits(cw_right[hs + j])
             for j in range(head_levels)]
        )
        if head_kind == "walk":
            (state, ctrl), leaf_order = _walk_phase(
                state, ctrl, cwp_head, cwl_head, cwr_head, None,
                r=head_levels, node_lanes=key_groups,
                leaf_order=leaf_order, compact=walk_compact,
            )
        else:
            state, ctrl = expand_head_planes_pallas(
                state, ctrl, cwp_head, cwl_head, cwr_head
            )
            leaf_order = tail_node_permutation(
                leaf_order, head_levels, leaf_order.size
            )[0]
        start = head_levels
    for i in range(start, a_levels):
        lvl = walk_levels + i
        if level_kernel:
            state, ctrl = expand_level_planes_pallas(
                state,
                ctrl,
                pack_key_planes(cw_seeds[lvl]),
                pack_key_bits(cw_left[lvl]),
                pack_key_bits(cw_right[lvl]),
            )
            continue
        groups2 = 2 * state.shape[-1]
        state, ctrl = expand_level_planes(
            state,
            ctrl,
            _tile_keys(pack_key_planes(cw_seeds[lvl]), groups2),
            _tile_keys(pack_key_bits(cw_left[lvl]), groups2 // 2),
            _tile_keys(pack_key_bits(cw_right[lvl]), groups2 // 2),
        )

    # The per-level phase appends [all-left; all-right] once per level.
    if a_levels > start:
        leaf_order = tail_node_permutation(
            leaf_order, a_levels - start, leaf_order.size
        )[0]

    # Leaf value blocks: output PRG + XOR value correction (party
    # negation is the identity for XOR shares).
    tile_nodes = tail_tile_nodes
    if tail_levels:
        # Fused tail: the last `tail_levels` levels AND the value hash,
        # one kernel launch per independent subtree tile.
        base = walk_levels + a_levels
        cwp_tail = jnp.stack(
            [pack_key_planes(cw_seeds[base + j])
             for j in range(tail_levels)]
        )
        cwl_tail = jnp.stack(
            [pack_key_bits(cw_left[base + j]) for j in range(tail_levels)]
        )
        cwr_tail = jnp.stack(
            [pack_key_bits(cw_right[base + j])
             for j in range(tail_levels)]
        )
        if tail_kind == "walk":
            (values, _), leaf_order = _walk_phase(
                state, ctrl, cwp_tail, cwl_tail, cwr_tail,
                pack_key_planes(last_vc),
                r=tail_levels, node_lanes=key_groups,
                leaf_order=leaf_order, compact=walk_compact,
                value_hash=True,
            )
        else:
            values, _ = expand_tail_planes_pallas(
                state,
                ctrl,
                cwp_tail,
                cwl_tail,
                cwr_tail,
                pack_key_planes(last_vc),
                tile_lanes=tile_nodes * key_groups,
            )
            leaf_order = tail_node_permutation(
                leaf_order, tail_levels, tile_nodes
            )[0]
    elif level_kernel:
        values = value_hash_planes_pallas(
            state, ctrl, pack_key_planes(last_vc)
        )
    else:
        values = mmo_hash_planes(fixed_keys.RK_VALUE, state)
        vc_p = _tile_keys(pack_key_planes(last_vc), values.shape[-1])
        values = values ^ (vc_p & ctrl[None, None, :])

    # Leave plane space once: [w * nkp, 4] node-major -> [nkp, w, 4].
    w = 1 << expand_levels
    out = planes_to_limbs(values).reshape(w, nkp, 4)
    out = jnp.moveaxis(out, 0, 1)
    if not bitrev_leaves:
        # The exit gather is argsort of the composed per-phase leaf
        # order (doubling phases append [all-left; all-right]; walk
        # phases emit natural offsets) — for pure doubling this equals
        # the classic bit-reversal permutation.
        perm = jnp.asarray(np.argsort(leaf_order))
        out = out[:, perm, :][:, :num_blocks, :]
        if out.shape[1] < num_blocks:
            # Blocks beyond the tree's capacity (mesh-padded databases)
            # can only select guaranteed-zero rows.
            out = jnp.pad(
                out, ((0, 0), (0, num_blocks - out.shape[1]), (0, 0))
            )
    return out[:nk]
