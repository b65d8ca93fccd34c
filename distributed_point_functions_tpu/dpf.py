"""Incremental Distributed Point Functions, TPU-native.

The capabilities of the reference's `DistributedPointFunction`
(`dpf/distributed_point_function.h:87`) rebuilt for JAX/XLA:

* **Key generation** runs host-side with Python-int arithmetic and the numpy
  AES oracle — it is O(tree depth) per key (sequential recurrence,
  `dpf/distributed_point_function.cc:121-222`), never a hot path.
* **Evaluation** runs on device. Full-domain expansion is a width-doubling
  sequence of jitted level steps (each level: one batched left+right MMO
  hash, masked correction, control-bit extraction — the TPU analog of
  `ExpandSeeds`, `dpf/distributed_point_function.cc:289-372`). Batched point
  evaluation walks all paths simultaneously with a `lax.scan` over levels and
  per-lane PRG-key selection (the analog of the Highway kernel
  `dpf/internal/evaluate_prg_hwy.cc:150-539`).
* **EvaluationContext** is the serializable checkpoint of a partially
  evaluated DPF (prefix -> (seed, control bit)), mirroring the proto
  `dpf/distributed_point_function.proto:156-171`; hierarchical evaluation
  resumes from it.

Control bits are carried as separate uint32 arrays; on the wire/in keys they
are embedded in seed LSBs exactly like the reference
(`dpf/internal/evaluate_prg_hwy.h:32-36` extract-and-clear convention).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import secrets
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import keys as fixed_keys
from .observability import tracing
from .observability.device import default_telemetry
from .ops import aes, limb
from .utils.profiling import scoped
from .value_types import IntType, ValueType

U32 = jnp.uint32
_U128_MASK = (1 << 128) - 1


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# ---------------------------------------------------------------------------
# Data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DpfParameters:
    """Parameters of one hierarchy level (`distributed_point_function.proto:92-105`)."""

    log_domain_size: int
    value_type: ValueType
    security_parameter: float = 0.0  # 0 => default 40 + log_domain_size


@dataclasses.dataclass
class CorrectionWord:
    """Per-tree-level correction (`distributed_point_function.proto:114-126`)."""

    seed: int  # uint128
    control_left: bool
    control_right: bool
    value_correction: Optional[list] = None


@dataclasses.dataclass
class DpfKey:
    """One party's DPF key (`distributed_point_function.proto:129-140`)."""

    seed: int  # uint128
    party: int
    correction_words: List[CorrectionWord]
    last_level_value_correction: list


@dataclasses.dataclass
class PartialEvaluations:
    """Array-backed (prefix -> seed, control bit) store at one tree level.

    The reference keeps partial evaluations in a btree keyed by prefix
    (`ComputePartialEvaluations`, `distributed_point_function.cc:374-476`);
    a Python dict of 128-bit ints costs ~1 µs/entry per level, which adds
    seconds per level at heavy-hitters scale (2^20 live prefixes). This
    store keeps sorted numpy arrays instead: writes are vectorized and
    lookups are one `searchsorted` per batch. Prefixes wider than 63 bits
    fall back to an object-dtype array (still sorted/searchable).
    """

    prefixes: np.ndarray  # sorted; uint64 or object (for >63-bit levels)
    seeds: np.ndarray  # uint32[n, 4] limbs
    control: np.ndarray  # uint32[n]

    def __len__(self) -> int:
        return len(self.prefixes)

    @classmethod
    def build(cls, prefixes_list, seeds: np.ndarray, control: np.ndarray):
        wide = any(p > 0x7FFFFFFFFFFFFFFF for p in prefixes_list)
        prefixes = np.array(
            prefixes_list, dtype=object if wide else np.uint64
        )
        order = np.argsort(prefixes, kind="stable")
        return cls(
            prefixes=prefixes[order],
            seeds=np.ascontiguousarray(seeds[order]),
            control=np.ascontiguousarray(control[order]),
        )

    @classmethod
    def from_dict(cls, d: Dict[int, Tuple[int, int]]):
        items = sorted(d.items())
        n = len(items)
        seeds = np.zeros((n, 4), dtype=np.uint32)
        control = np.zeros((n,), dtype=np.uint32)
        for i, (_, (seed, t)) in enumerate(items):
            seeds[i] = aes.u128_to_limbs(seed)
            control[i] = t
        return cls.build([p for p, _ in items], seeds, control)

    def lookup(self, wanted_list):
        """Seeds/control rows for `wanted_list` prefixes (vectorized);
        raises ValueError naming the first missing prefix."""
        wide = self.prefixes.dtype == object
        try:
            wanted = np.array(
                wanted_list, dtype=object if wide else np.uint64
            )
        except OverflowError:
            # A >64-bit prefix cannot be in a uint64-keyed store.
            missing = next(
                p for p in wanted_list if p > 0xFFFFFFFFFFFFFFFF
            )
            raise ValueError(
                f"prefix {int(missing)} not present in "
                f"ctx.partial_evaluations"
            ) from None
        pos = np.searchsorted(self.prefixes, wanted)
        pos_clipped = np.minimum(pos, len(self.prefixes) - 1)
        ok = (pos < len(self.prefixes)) & (
            self.prefixes[pos_clipped] == wanted
        )
        if not np.all(ok):
            missing = wanted[np.argmin(ok)]
            raise ValueError(
                f"prefix {int(missing)} not present in "
                f"ctx.partial_evaluations"
            )
        return self.seeds[pos_clipped], self.control[pos_clipped]

    def items(self):
        """(prefix, (seed uint128, control)) pairs in sorted order."""
        for i in range(len(self.prefixes)):
            yield int(self.prefixes[i]), (
                aes.limbs_to_u128(self.seeds[i]),
                int(self.control[i]),
            )


@dataclasses.dataclass
class EvaluationContext:
    """Checkpoint of a partially evaluated DPF (proto `:156-171`)."""

    key: DpfKey
    previous_hierarchy_level: int = -1
    # (prefix -> seed uint128, control bit) at tree level
    # hierarchy_to_tree[partial_evaluations_level]: a PartialEvaluations
    # store, a plain dict (accepted for compatibility), or empty.
    partial_evaluations: object = dataclasses.field(default_factory=dict)
    partial_evaluations_level: int = 0


@dataclasses.dataclass
class BatchCutState:
    """Device-resident walk states of MANY keys at one hierarchy level.

    The multi-key analog of `EvaluationContext.partial_evaluations`: for
    every (key, prefix) pair the pre-value-hash seed and control bit at
    the hierarchy level's tree level. `evaluate_prefixes_batch` returns
    one of these after every level so the next level's evaluation walks
    only the new tree levels instead of re-expanding from the root —
    the state reuse the heavy-hitters level-synchronized sweep is built
    on. `prefixes` are the *domain indices* the state was evaluated at,
    strictly ascending; a later call may resume from any subset's
    children (survivors of threshold pruning).
    """

    hierarchy_level: int
    prefixes: np.ndarray  # sorted domain indices; uint64 or object
    seeds: jnp.ndarray  # uint32[num_keys, num_prefixes, 4]
    control: jnp.ndarray  # uint32[num_keys, num_prefixes]

    @property
    def num_keys(self) -> int:
        return self.seeds.shape[0]

    def __len__(self) -> int:
        return len(self.prefixes)

    def positions(self, wanted_list) -> np.ndarray:
        """Positions of `wanted_list` prefixes in this state; raises
        ValueError naming the first missing one."""
        wide = self.prefixes.dtype == object
        wanted = np.array(wanted_list, dtype=object if wide else np.uint64)
        pos = np.searchsorted(self.prefixes, wanted)
        pos_clipped = np.minimum(pos, len(self.prefixes) - 1)
        ok = (pos < len(self.prefixes)) & (
            self.prefixes[pos_clipped] == wanted
        )
        if not np.all(ok):
            missing = wanted[np.argmin(ok)]
            raise ValueError(
                f"prefix {int(missing)} not present in cut state at "
                f"hierarchy level {self.hierarchy_level}"
            )
        return pos_clipped.astype(np.int64)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _gather_cut_lanes_donated(seeds, control, pos):
    """Resume gather with the previous level's cut buffers donated: the
    caller asserts this is the LAST read of that `BatchCutState`, so
    XLA may reuse its HBM for the new frontier instead of holding both
    levels' cut tensors live (the cut-state cache is the largest
    retained buffer of a heavy-hitters sweep). On backends without
    donation support this is a plain gather (warning filtered in
    `pir.dense_eval`)."""
    return jnp.take(seeds, pos, axis=1), jnp.take(control, pos, axis=1)


def _build_fused_accumulate(plan, vt, blocks_needed):
    """One jitted program: multi-level walk + per-level value extraction
    + masked accumulation (the fused engine behind
    `evaluate_and_accumulate`). `plan` is a static tuple of
    (start_tree_level, stop_tree_level, path-bit indices) per hierarchy
    level.

    The trailing run of uniform levels (exactly one tree level walked,
    same blocks-needed) runs as a `lax.scan` — unrolling all levels made
    XLA compile time scale with the domain's bit width (~3 min at 32
    levels on the 1-vCPU host), which a scan body amortizes to one
    level."""
    tail_start = len(plan)
    for i in range(len(plan) - 1, -1, -1):
        s_, e_, _ = plan[i]
        if e_ - s_ == 1 and blocks_needed[i] == blocks_needed[-1]:
            tail_start = i
        else:
            break

    # The walk below runs inside this program's jit trace, where the
    # level kernels' on-device self-check cannot run; run it eagerly
    # first so a miscompiling kernel raises here.
    from .pir.dense_eval_planes import warm_level_kernels

    warm_level_kernels()

    def level_values(seeds, control, parties, vc, blk, bn):
        values = _leaf_stage_at(seeds, control, vc, blk, vt, bn, -1)
        return vt.dev_where(parties != 0, vt.dev_neg(values), values)

    @jax.jit
    def run(seeds, parties, paths, cw_seeds, cw_left, cw_right, vcs,
            masks, blks):
        control = parties
        n = seeds.shape[0]
        acc = vt.dev_zeros((n,))
        for hl, (start, stop, bits) in enumerate(plan[:tail_start]):
            if stop > start:
                seeds, control = _eval_paths(
                    seeds,
                    control,
                    paths,
                    cw_seeds[start:stop],
                    cw_left[start:stop],
                    cw_right[start:stop],
                    jnp.asarray(np.array(bits, dtype=np.int32)),
                )
            values = level_values(
                seeds, control, parties, vcs[hl], blks[hl],
                blocks_needed[hl],
            )
            acc = vt.dev_where(masks[hl], vt.dev_add(acc, values), acc)
        if tail_start < len(plan):
            t0 = tail_start
            lo = plan[t0][0]
            bit_arr = jnp.asarray(
                np.array([p[2][0] for p in plan[t0:]], dtype=np.int32)
            )
            vcs_tail = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=0), *vcs[t0:]
            )
            xs = (
                cw_seeds[lo : plan[-1][1]],
                cw_left[lo : plan[-1][1]],
                cw_right[lo : plan[-1][1]],
                bit_arr,
                vcs_tail,
                masks[t0:],
                blks[t0:],
            )

            def body(carry, x):
                seeds, control, acc = carry
                cw_s, cw_l, cw_r, bit, vc, mask, blk = x
                seeds, control = _eval_paths(
                    seeds, control, paths,
                    cw_s[None], cw_l[None], cw_r[None], bit[None],
                )
                values = level_values(
                    seeds, control, parties, vc, blk, blocks_needed[-1]
                )
                acc = vt.dev_where(mask, vt.dev_add(acc, values), acc)
                return (seeds, control, acc), None

            (seeds, control, acc), _ = lax.scan(
                body, (seeds, control, acc), xs
            )
        return acc

    return run


@dataclasses.dataclass
class StagedKeyBatch:
    """A batch of DPF keys staged to device arrays, once.

    The multi-key engine (`evaluate_and_apply`, the analog of the per-seed
    correction-word mode of `evaluate_prg_hwy.h:58-65`) previously restaged
    every key's correction words and value corrections through per-key
    Python loops on every call and level; for the DCF benchmark config
    (2^32 domain x 256 keys) that host staging dominated runtime. Staging
    assembles plain numpy arrays in one pass over the keys and does a
    single device transfer; the arrays then serve any number of
    evaluations (DCF reuses one batch across both its shifted evaluation
    points; MIC across all its intervals).
    """

    n: int
    seeds: jnp.ndarray  # uint32[n, 4]
    parties: jnp.ndarray  # uint32[n]
    cw_seeds: jnp.ndarray  # uint32[L, n, 4], L = tree levels - 1
    cw_left: jnp.ndarray  # uint32[L, n]
    cw_right: jnp.ndarray  # uint32[L, n]
    value_corrections: list  # per hierarchy level: pytree, leaves [n, epb, ...]


# ---------------------------------------------------------------------------
# Host-side AES helpers (numpy oracle; keygen only)
# ---------------------------------------------------------------------------


def _mmo_host(rk: np.ndarray, xs: Sequence[int]) -> List[int]:
    blocks = np.stack([aes.u128_to_limbs(x) for x in xs])
    out = aes.mmo_hash_np(rk, blocks)
    return [aes.limbs_to_u128(out[i]) for i in range(out.shape[0])]


def _value_hash_bytes_host(seed: int, num_blocks: int) -> bytes:
    xs = [(seed + j) & _U128_MASK for j in range(num_blocks)]
    outs = _mmo_host(fixed_keys.RK_VALUE, xs)
    return b"".join(x.to_bytes(16, "little") for x in outs)


# ---------------------------------------------------------------------------
# Jitted device stages
# ---------------------------------------------------------------------------

# Mask clearing the control-bit LSB of a 128-bit limb block
# (`ExtractAndClearLowestBit`, `evaluate_prg_hwy.h:32-36`).
_CLEAR_LSB = np.array(
    [0xFFFFFFFE, 0xFFFFFFFF, 0xFFFFFFFF, 0xFFFFFFFF], dtype=np.uint32
)


def _expand_level_body(seeds, control, cw_seed, cw_left, cw_right):
    """One breadth-first expansion level: [n] seeds -> [2n] seeds.

    seeds: uint32[n, 4]; control: uint32[n]; cw_seed: uint32[4];
    cw_left/right: uint32 scalars. TPU analog of the reference's
    `ExpandSeeds` inner loop (`distributed_point_function.cc:327-370`).
    Left and right children come from ONE key-selected AES pass (even lanes
    left, odd lanes right) to keep the compiled graph small — the analog of
    the reference's per-lane key masking
    (`aes_128_fixed_key_hash_hwy.h:123-155`).
    """
    n = seeds.shape[0]
    doubled = jnp.repeat(seeds, 2, axis=0)  # [2n, 4]
    sel = jnp.tile(jnp.arange(2, dtype=U32), n)  # [2n]
    h = aes.mmo_hash_select(
        fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, sel, doubled
    )
    control2 = jnp.repeat(control, 2, axis=0)
    h = h ^ jnp.where(control2[:, None] != 0, cw_seed[None, :], U32(0))
    t_new = h[:, 0] & U32(1)
    h = h & jnp.asarray(_CLEAR_LSB)
    cw_dir = jnp.where(sel != 0, cw_right, cw_left)
    t_new = t_new ^ (control2 * cw_dir)
    return h, t_new


_expand_level = jax.jit(_expand_level_body)


@functools.lru_cache(maxsize=None)
def _expand_levels_limb_fn(num_levels: int, hash_leaves: bool = False):
    """One jitted program running `num_levels` width-doubling expansion
    levels (the whole `ExpandSeeds` loop fused; widths double per level so
    a scan cannot carry them — the unroll specializes per level count,
    cached across calls). With `hash_leaves` the leaf seeds come back
    already passed through the value MMO hash (single-block value types:
    fuses `HashExpandedSeeds` into the same program)."""

    @jax.jit
    @scoped("dpf.expand")
    def run(seeds, control, cw_seeds, cw_left, cw_right):
        for i in range(num_levels):
            seeds, control = _expand_level_body(
                seeds, control, cw_seeds[i], cw_left[i], cw_right[i]
            )
        if hash_leaves:
            seeds = aes.mmo_hash(fixed_keys.RK_VALUE, seeds)
        return seeds, control

    return run


def _limb_levels(p: int, num_levels: int) -> int:
    """How many of `num_levels` levels the plane program runs in limb
    space from `p` seeds. Plane layout carries its padding through every
    level (dead lanes double along with live ones), so entering at the
    root from p=1 seed would do 32x the AES work forever: the first
    levels stay in limb space until the width fills the 32-block words
    (exactly, or within ~12% for non-power-of-two prefix counts)."""
    limb_levels = 0
    while limb_levels < num_levels:
        width = p << limb_levels
        if width % 32 == 0 or width >= 256:
            break
        limb_levels += 1
    return limb_levels


def _bitrev_order(bits: int) -> np.ndarray:
    """order[position] = path for `bits` doubling levels that append
    [all-left; all-right]: the bit reversal of the position."""
    pos = np.arange(1 << bits)
    path = np.zeros_like(pos)
    for i in range(bits):
        path |= ((pos >> i) & 1) << (bits - 1 - i)
    return path


def _gather_exit(state, ctrl, order, n0):
    """Leaves in the limb program's layout: uint32[n0 * 2^PL, 4] seeds
    and uint32[n0 * 2^PL] control bits in natural (prefix-major) order,
    by a trace-time-static gather of single leaves."""
    from .ops.aes_bitslice import planes_to_limbs

    n32 = 32 * ctrl.shape[0] // order.size
    out = planes_to_limbs(state)  # [2^PL * n32, 4], lane-ordered
    shifts = jnp.arange(32, dtype=U32)
    ctrl_bits = ((ctrl[:, None] >> shifts) & U32(1)).reshape(-1)
    pos = np.argsort(order)
    path = np.arange(order.size)
    lane = pos[path][:, None] * n32 + np.arange(n0)[None, :]
    perm = jnp.asarray(
        np.ascontiguousarray(lane.T.reshape(-1))  # prefix-major
    )
    return out[perm], ctrl_bits[perm]


def _direct_exit(state, ctrl, order, n0):
    """Leaves as the lane-dense leaf stage (`_leaf_stage_lanes`) takes
    them, with no reorder: uint32[4, A, n0, B] limbs, limbs leading and
    the path positions on the last axis (position = a * B + b, so one
    position's n0 <= 32 prefixes are one word of every plane), and the
    packed control words uint32[A * B]. The positions stay in emission
    order, the bit reversal of the path; the leaf stage undoes it on
    whole rows while it lays out the output."""
    from .ops.aes_bitslice import planes_to_limb_rows

    if ctrl.shape[0] != order.size or not np.array_equal(
        order, _bitrev_order(order.size.bit_length() - 1)
    ):
        raise ValueError(
            "the direct exit needs one 32-prefix word per path position "
            "and the doubling levels' order"
        )
    lanes = min(128, order.size)
    return planes_to_limb_rows(state, lanes)[:, :, :n0], ctrl


@functools.lru_cache(maxsize=None)
def _expand_levels_planes_fn(num_levels: int, level_kernel: bool = False,
                             hash_leaves: bool = False,
                             tail_req: int = 0,
                             tail_tile_target: int = 0,
                             head_req: int = 0,
                             head_cap: int = 0,
                             tail_kind: str = "concat",
                             head_kind: str = "concat",
                             walk_compact: bool = False,
                             exit: str = "gather"):
    """`_expand_levels_limb_fn` computed in bitsliced plane layout (see
    `pir/dense_eval_planes.py` for the design): children are appended
    [all-left; all-right] per level so the lane order ends up
    path-bit-reversed and prefix-minor. With `exit="gather"` a
    trace-time-static gather restores the natural interleaved order,
    making the output bit-identical to the limb program; with
    `exit="direct"` the leaves leave as `_direct_exit` describes, for
    the lane-dense leaf stage (pure doubling, n0 <= 32 prefixes and
    `hash_leaves` only). Shared correction words only (one key), like
    the limb program. With `level_kernel` each level runs the fused
    Pallas VMEM kernel (`ops/expand_planes_pallas.py`); with
    `hash_leaves` the leaf value MMO hash runs before leaving plane
    layout (no extra transpose round-trip for single-block value
    types)."""

    @jax.jit
    @scoped("dpf.expand")
    def run(seeds, control, cw_seeds, cw_left, cw_right):
        from .ops.aes_bitslice import (
            broadcast_cw_planes,
            limbs_to_planes,
            mmo_hash_planes,
            pack_select_bits,
        )
        from .ops.expand_planes_pallas import (
            expand_level_planes_pallas,
            value_hash_planes_pallas,
        )
        from .pir.dense_eval_planes import expand_level_planes

        limb_levels = _limb_levels(seeds.shape[0], num_levels)
        for i in range(limb_levels):
            seeds, control = _expand_level_body(
                seeds, control, cw_seeds[i], cw_left[i], cw_right[i]
            )

        n0 = seeds.shape[0]
        pad = (-n0) % 32
        if pad:
            seeds = jnp.pad(seeds, ((0, pad), (0, 0)))
            control = jnp.pad(control, ((0, pad),))
        n32 = n0 + pad

        state = limbs_to_planes(seeds)
        ctrl = pack_select_bits(control.astype(U32))

        plane_levels = num_levels - limb_levels
        # Fused tail (last levels + leaf hash in one VMEM kernel per
        # subtree tile): only meaningful when the leaf hash is fused
        # anyway, and node tiles split on prefix-word boundaries. The
        # env knobs are read at dispatch time (_expand_levels_fn) and
        # arrive as cache keys, so the trace never bakes stale values.
        tail_r = tile_nodes = 0
        if tail_req and level_kernel and hash_leaves and plane_levels > 0:
            if tail_kind == "walk":
                # The walk tail tiles internally at constant width; no
                # entry-tile floor applies.
                tail_r = min(tail_req, plane_levels)
            else:
                from .pir.dense_eval_planes import _tail_split

                tail_r, tile_nodes = _tail_split(
                    n32 // 32, plane_levels,
                    requested_levels=tail_req,
                    target_lanes=tail_tile_target,
                )
        # Fused head (first plane levels in one launch over the narrow
        # width): head_req/head_cap arrive as dispatch-time cache keys
        # like the tail knobs, so the trace never bakes stale env state.
        head_r = 0
        if head_req and level_kernel and plane_levels - tail_r > 0:
            avail = plane_levels - tail_r
            if head_req > 0:
                head_r = min(head_req, avail)
            else:
                from .pir.dense_eval_planes import _auto_head_count

                head_r = _auto_head_count(head_cap, n32 // 32, avail)
        if head_r:
            from .ops.expand_planes_pallas import (
                expand_head_planes_pallas,
                walk_descend_planes_pallas,
            )

            h0 = limb_levels
            cwp_head = jnp.stack(
                [broadcast_cw_planes(cw_seeds[h0 + j])
                 for j in range(head_r)]
            )
            cwl_head = jnp.stack(
                [(U32(0) - (cw_left[h0 + j] & U32(1)))[None]
                 for j in range(head_r)]
            )
            cwr_head = jnp.stack(
                [(U32(0) - (cw_right[h0 + j] & U32(1)))[None]
                 for j in range(head_r)]
            )
            if head_kind == "walk":
                from .ops.expand_planes_pallas import walk_plan

                nl = n32 // 32
                tile_h, compact_h, _ = walk_plan(
                    state.shape[-1] << head_r, 1, nl, head_r,
                    walk_compact,
                )
                state, ctrl = walk_descend_planes_pallas(
                    state, ctrl, cwp_head, cwl_head, cwr_head,
                    r=head_r, tile_lanes=tile_h, node_lanes=nl,
                    compact_entry=compact_h,
                )
            else:
                state, ctrl = expand_head_planes_pallas(
                    state, ctrl, cwp_head, cwl_head, cwr_head
                )
        for i in range(limb_levels + head_r, num_levels - tail_r):
            if level_kernel:
                state, ctrl = expand_level_planes_pallas(
                    state,
                    ctrl,
                    broadcast_cw_planes(cw_seeds[i]),
                    (U32(0) - (cw_left[i] & U32(1)))[None],
                    (U32(0) - (cw_right[i] & U32(1)))[None],
                )
            else:
                state, ctrl = expand_level_planes(
                    state,
                    ctrl,
                    broadcast_cw_planes(cw_seeds[i]),
                    U32(0) - (cw_left[i] & U32(1)),
                    U32(0) - (cw_right[i] & U32(1)),
                )

        if tail_r:
            from .ops.expand_planes_pallas import (
                expand_tail_planes_pallas,
                walk_descend_planes_pallas,
            )

            base = num_levels - tail_r
            cwp_tail = jnp.stack(
                [broadcast_cw_planes(cw_seeds[base + j])
                 for j in range(tail_r)]
            )
            cwl_tail = jnp.stack(
                [(U32(0) - (cw_left[base + j] & U32(1)))[None]
                 for j in range(tail_r)]
            )
            cwr_tail = jnp.stack(
                [(U32(0) - (cw_right[base + j] & U32(1)))[None]
                 for j in range(tail_r)]
            )
            # Zero value-correction planes: the kernel reduces to the
            # pure MMO output hash (correction is arithmetic here and
            # stays in the leaf stage).
            if tail_kind == "walk":
                from .ops.expand_planes_pallas import walk_plan

                nl = n32 // 32
                tile_t, compact_t, _ = walk_plan(
                    state.shape[-1] << tail_r, 1, nl, tail_r,
                    walk_compact,
                )
                state, ctrl = walk_descend_planes_pallas(
                    state, ctrl, cwp_tail, cwl_tail, cwr_tail,
                    jnp.zeros((16, 8, 1), dtype=U32),
                    r=tail_r, tile_lanes=tile_t, value_hash=True,
                    node_lanes=nl, compact_entry=compact_t,
                )
            else:
                state, ctrl = expand_tail_planes_pallas(
                    state,
                    ctrl,
                    cwp_tail,
                    cwl_tail,
                    cwr_tail,
                    jnp.zeros((16, 8, 1), dtype=U32),
                    tile_lanes=tile_nodes * (n32 // 32),
                )
        elif hash_leaves:
            if level_kernel:
                # (same zero-correction reduction as the tail)
                zeros_vc = jnp.zeros((16, 8, 1), dtype=U32)
                state = value_hash_planes_pallas(state, ctrl, zeros_vc)
            else:
                state = mmo_hash_planes(fixed_keys.RK_VALUE, state)
        # lane(path, prefix) = position(path) * n32 + prefix over the
        # plane levels only (the limb prefix is already natural/
        # interleaved); natural index = prefix * 2^PL + path. Static per
        # specialization. Without the tail, position = bit-reversal; the
        # tiled tail composes per-tile plane order on top.
        from .ops.expand_planes_pallas import (
            compose_walk_leaf_order,
            tail_node_permutation,
            walk_plan,
        )

        # Compose each phase's node order (walk phases emit natural
        # offsets, or offset-major tiles in compact mode; doubling
        # phases append [all-left; all-right]); the gather exit is
        # argsort of the composition. Pure doubling degenerates to the
        # classic bit-reversal. walk_plan mirrors the kernel call
        # sites exactly, so the order can never disagree with the
        # launched tiles.
        nl = n32 // 32

        def walk_order(order, r):
            _, compact, npt = walk_plan(
                order.size * nl << r, 1, nl, r, walk_compact
            )
            return compose_walk_leaf_order(order, r, compact, npt)

        order = np.zeros(1, dtype=np.int64)
        if head_r:
            if head_kind == "walk":
                order = walk_order(order, head_r)
            else:
                order = tail_node_permutation(order, head_r, order.size)[0]
        mid = plane_levels - head_r - tail_r
        if mid > 0:
            order = tail_node_permutation(order, mid, order.size)[0]
        if tail_r:
            if tail_kind == "walk":
                order = walk_order(order, tail_r)
            else:
                order = tail_node_permutation(order, tail_r, tile_nodes)[0]
        if exit == "direct":
            return _direct_exit(state, ctrl, order, n0)
        return _gather_exit(state, ctrl, order, n0)

    return run


def _planes_plan(hash_leaves: bool):
    """The plane program's knobs (`_expand_levels_planes_fn` keyword
    arguments), or None for the limb program: `DPF_TPU_EXPAND_LEVELS` =
    `limb` | `planes` | `auto` (default: planes on TPU, limb elsewhere).
    On TPU the plane levels run the fused Pallas kernels
    (`DPF_TPU_LEVEL_KERNEL`); a kernel failure raises."""
    from .utils.runtime import planes_selected

    if not planes_selected("DPF_TPU_EXPAND_LEVELS"):
        return None
    from .pir import dense_eval_planes as _dep

    mode = _dep._level_kernel_enabled()
    if not mode:
        return {}
    kinds = {}
    if mode == "walk":
        kinds = {
            "tail_kind": "walk",
            "head_kind": "walk",
            "walk_compact": _dep._walk_compact_enabled(),
        }
    if mode in ("tail", "walk") and hash_leaves:
        # Knobs only enter the cache key when the tail can actually run
        # (hash_leaves), so no-tail programs aren't re-traced per tuple.
        tail_req = _dep._tail_levels_requested()
        tail_tile = _dep._tail_tile_target()
    else:
        tail_req, tail_tile = 0, 0
    # Head knobs, resolved at dispatch time (the jitted program only
    # sees the cache keys): DPF_TPU_HEAD_LEVELS forces a count; walk
    # mode's head (the walk kernel family) fills to head_cap lanes; the
    # concat head is not in the TPU auto set.
    raw_head = os.environ.get("DPF_TPU_HEAD_LEVELS", "auto")
    head_cap = _dep._head_max_lanes()
    if raw_head != "auto":
        try:
            head_req = max(0, int(raw_head))
        except ValueError:
            head_req = 0
    else:
        head_req = -1 if mode == "walk" else 0
    return dict(level_kernel=True, tail_req=tail_req,
                tail_tile_target=tail_tile, head_req=head_req,
                head_cap=head_cap, **kinds)


def _leaf_exit(p: int, num_levels: int) -> str:
    """The exit a hash-fused expansion of `num_levels` levels from `p`
    seeds takes: "direct" where the plane program only doubles (no
    fused head, tail or walk phase) and one 32-prefix word holds every
    prefix, else "gather"."""
    plan = _planes_plan(hash_leaves=True)
    if (plan is None or num_levels == 0 or plan.get("tail_req")
            or plan.get("head_req") or plan.get("tail_kind")
            or (p << _limb_levels(p, num_levels)) > 32):
        return "gather"
    return "direct"


def _expand_levels_fn(num_levels: int, hash_leaves: bool = False,
                      exit: str = "gather"):
    """Dispatch the fused expansion program (`_planes_plan`): the limb
    program or the plane program with the given `exit`. `hash_leaves`
    fuses the leaf value MMO hash into the same program (single-block
    value types)."""
    plan = _planes_plan(hash_leaves)
    if plan is None:
        if exit != "gather":
            raise ValueError("the limb program has only the gather exit")
        return _expand_levels_limb_fn(num_levels, hash_leaves=hash_leaves)
    return _expand_levels_planes_fn(num_levels, hash_leaves=hash_leaves,
                                    exit=exit, **plan)


@jax.jit
def _eval_paths_limb(
    seeds, control, paths, cw_seeds, cw_left, cw_right, bit_indices
):
    """Walk `L` tree levels for a batch of paths simultaneously.

    seeds: uint32[n, 4]; control: uint32[n]; paths: uint32[n, 4];
    cw_seeds: uint32[L, m, 4]; cw_left/right: uint32[L, m] with m == 1
    (shared correction words) or m == n (per-seed, the multi-key batch mode
    of `evaluate_prg_hwy.h:58-65`); bit_indices: int32[L].

    One `lax.scan` step = one level: per-lane PRG-key selection by path bit
    (single AES pass), masked seed correction, control-bit extract/clear.
    """

    clear = jnp.asarray(_CLEAR_LSB)

    def body(carry, x):
        seeds, control = carry
        cw_seed, cw_l, cw_r, bit_index = x
        pbit = limb.get_bit(paths, bit_index)  # uint32[n]
        h = aes.mmo_hash_select(
            fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, pbit, seeds
        )
        corr = jnp.where(control[:, None] != 0, cw_seed, U32(0))
        h = h ^ corr
        t_new = h[:, 0] & U32(1)
        h = h & clear
        cw_dir = jnp.where(pbit != 0, cw_r, cw_l)
        t_new = t_new ^ (control * cw_dir)
        return (h, t_new), None

    (seeds, control), _ = lax.scan(
        body, (seeds, control), (cw_seeds, cw_left, cw_right, bit_indices)
    )
    return seeds, control


@functools.partial(jax.jit, static_argnames=("level_kernel",))
def _eval_paths_planes(
    seeds, control, paths, cw_seeds, cw_left, cw_right, bit_indices,
    level_kernel: bool = False,
):
    """`_eval_paths_limb` computed in bitsliced plane layout: one
    transpose in, per level a packed-select-mask AES (no per-level
    transposes — the path-walk analog of
    `dense_eval_planes.evaluate_selection_blocks_planes`), one transpose
    out. Bit-identical to the limb kernel in both correction-word modes.
    With `level_kernel` each level runs the fused Pallas select-key AES
    kernel (`ops/expand_planes_pallas.py:path_level_planes_pallas`).
    """
    from .ops.aes_bitslice import (
        aes_rounds_select_planes,
        limbs_to_planes,
        pack_select_bits,
        planes_to_limbs,
        sigma_planes,
    )
    from .pir.dense_eval_planes import pack_key_bits, pack_key_planes

    n = seeds.shape[0]
    per_seed = cw_seeds.shape[1] == n and n != 1
    pad = (-n) % 32
    if pad:
        seeds = jnp.pad(seeds, ((0, pad), (0, 0)))
        control = jnp.pad(control, ((0, pad),))
        paths = jnp.pad(paths, ((0, pad), (0, 0)))
        if per_seed:
            cw_seeds = jnp.pad(cw_seeds, ((0, 0), (0, pad), (0, 0)))
            cw_left = jnp.pad(cw_left, ((0, 0), (0, pad)))
            cw_right = jnp.pad(cw_right, ((0, 0), (0, pad)))
    np32 = n + pad
    groups = np32 // 32

    state0 = limbs_to_planes(seeds)
    ctrl0 = pack_key_bits(control.astype(U32))
    shifts = jnp.arange(32, dtype=U32)

    def cw_planes(cw_seed, cw_l, cw_r):
        """Per-level packed correction planes/words for both modes."""
        if per_seed:
            return (
                pack_key_planes(cw_seed),        # [16, 8, groups]
                pack_key_bits(cw_l),             # [groups]
                pack_key_bits(cw_r),
            )
        # Shared correction words: every lane uses the same bit, so the
        # packed word is all-ones or all-zeros.
        from .ops.aes_bitslice import broadcast_cw_planes

        return (
            broadcast_cw_planes(cw_seed[0]),      # broadcasts over groups
            (U32(0) - (cw_l[0] & U32(1)))[None],
            (U32(0) - (cw_r[0] & U32(1)))[None],
        )

    def body(carry, x):
        state, ctrl = carry
        cw_seed, cw_l, cw_r, bit_index = x
        pbit = limb.get_bit(paths, bit_index)  # uint32[np32]
        sel = pack_select_bits(pbit)           # [groups]
        cwp, cwl_w, cwr_w = cw_planes(cw_seed, cw_l, cw_r)
        if level_kernel:
            from .ops import expand_planes_pallas as _epp

            state, ctrl = _epp.path_level_planes_pallas(
                state, ctrl, sel, cwp, cwl_w, cwr_w, per_seed=per_seed
            )
            return (state, ctrl), None
        sig = sigma_planes(state)
        h = aes_rounds_select_planes(
            fixed_keys.RK_LEFT, fixed_keys.RK_RIGHT, sel, sig
        ) ^ sig
        h = h ^ (cwp & ctrl[None, None, :])
        t_new = h[0, 0]
        h = h.at[0, 0].set(jnp.zeros_like(t_new))
        cw_dir = (sel & cwr_w) | (~sel & cwl_w)
        ctrl = t_new ^ (ctrl & cw_dir)
        return (h, ctrl), None

    (state, ctrl), _ = lax.scan(
        body, (state0, ctrl0), (cw_seeds, cw_left, cw_right, bit_indices)
    )
    out = planes_to_limbs(state)
    ctrl_bits = (ctrl[:, None] >> shifts) & U32(1)  # [groups, 32]
    control_out = ctrl_bits.reshape(np32)
    return out[:n], control_out[:n]


def _eval_paths(seeds, control, paths, cw_seeds, cw_left, cw_right,
                bit_indices):
    """Dispatch the path walk: `DPF_TPU_EVAL_PATHS` = `limb` | `planes` |
    `auto` (default: planes on TPU, limb elsewhere — same trade-off as
    `dense_eval.expansion_impl`). On TPU the plane levels run the fused
    Pallas select-key kernel (`DPF_TPU_LEVEL_KERNEL`); a kernel failure
    raises."""
    from .utils.runtime import planes_selected

    if not planes_selected("DPF_TPU_EVAL_PATHS"):
        return _eval_paths_limb(
            seeds, control, paths, cw_seeds, cw_left, cw_right, bit_indices
        )
    from .pir import dense_eval_planes as _dep

    return _eval_paths_planes(
        seeds, control, paths, cw_seeds, cw_left, cw_right, bit_indices,
        level_kernel=bool(_dep._level_kernel_enabled()),
    )


@functools.partial(jax.jit, static_argnames=("num_blocks",))
def _value_hash(seeds, num_blocks):
    """H_value(seed + j) for j < num_blocks: uint32[n,4] -> uint32[n,B,4].

    The iota-offset output PRG of `HashExpandedSeeds`
    (`distributed_point_function.cc:523-547`).
    """
    offs = [limb.add_scalar(seeds, j) for j in range(num_blocks)]
    stacked = jnp.stack(offs, axis=1)  # [n, B, 4]
    return aes.mmo_hash(fixed_keys.RK_VALUE, stacked)


@functools.partial(
    jax.jit,
    static_argnames=("vtype", "cepb", "num_blocks", "party", "pre_hashed"),
)
@scoped("dpf.leaf_values")
def _leaf_stage(seeds, control, vc_dev, vtype, cepb, num_blocks, party,
                pre_hashed=False):
    """Hash seeds into value blocks, parse, apply value correction.

    Returns a value pytree with batch shape [n, cepb] (the first
    `corrected_elements_per_block` elements of each block, mirroring
    `EvaluateUntil`'s correction loop, `distributed_point_function.h:838-862`).
    With `pre_hashed` (single-block value types) `seeds` are already the
    value-hashed blocks from the fused expansion.
    """
    if pre_hashed:
        if num_blocks != 1:
            raise ValueError("pre_hashed requires single-block values")
        blocks = seeds[:, None, :]
    else:
        blocks = _value_hash(seeds, num_blocks)
    values = vtype.dev_from_value_blocks(blocks)  # [n, epb, ...]
    values = jax.tree_util.tree_map(lambda x: x[:, :cepb], values)
    vc = jax.tree_util.tree_map(lambda x: x[None, :cepb], vc_dev)
    mask = jnp.broadcast_to(
        (control != 0)[:, None], seeds.shape[:1] + (cepb,)
    )
    vc_b = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, mask.shape + x.shape[2:]), vc
    )
    corrected = vtype.dev_where(mask, vtype.dev_add(values, vc_b), values)
    if party == 1:
        corrected = vtype.dev_neg(corrected)
    return corrected


def _add_limb_rows(a, b):
    """Carry-propagating add of two little-endian limb lists (each limb
    an array, so the leaves stay on the lanes)."""
    out = []
    carry = None
    for x, y in zip(a, b):
        s = x + y
        c = (s < x).astype(U32)
        if carry is not None:
            s2 = s + carry
            c = c | (s2 < s).astype(U32)
            s = s2
        out.append(s)
        carry = c
    return out


def _neg_limb_rows(a):
    """Two's complement of a little-endian limb list."""
    out = []
    carry = U32(1)
    for x in a:
        s = ~x + carry
        carry = (s < carry).astype(U32)
        out.append(s)
    return out


@functools.partial(jax.jit, static_argnames=("bits", "cepb", "party"))
@scoped("dpf.leaf_values")
def _leaf_stage_lanes(limbs, ctrl, vc, bits, cepb, party):
    """`_leaf_stage` and the flatten for `IntType(bits)` values, on the
    direct exit's lane-dense limbs (`_direct_exit`): parse the first
    `cepb` elements of each hashed block, add the value correction
    uint32[cepb, nlimbs] where the control bit is set, negate for party
    1, and return uint32[n0 * 2^PL * cepb, nlimbs] in domain order, the
    leaves' bit-reversed positions restored by two gathers of whole rows
    around one transpose that also interleaves the elements."""
    _, a_rows, n0, b_lanes = limbs.shape
    nlimbs = max(1, bits // 32)
    low = U32((1 << bits) - 1) if bits < 32 else None
    shifts = jnp.arange(n0, dtype=U32)[None, :, None]
    mask = ((ctrl.reshape(a_rows, 1, b_lanes) >> shifts) & U32(1)) != 0
    elems = []
    for e in range(cepb):
        if low is None:
            v = [limbs[e * nlimbs + h] for h in range(nlimbs)]
        else:
            v = [(limbs[e * bits // 32] >> U32(e * bits % 32)) & low]
        c = _add_limb_rows(v, [vc[e, h] for h in range(nlimbs)])
        v = [jnp.where(mask, x, y) for x, y in zip(c, v)]
        if party == 1:
            v = _neg_limb_rows(v)
        if low is not None:
            v = [v[0] & low]
        elems.append(jnp.stack(v))  # [nlimbs, A, n0, B]
    # path = bitrev(b) * A + bitrev(a) for position a * B + b.
    z = jnp.stack(elems, axis=2)[:, _bitrev_order(a_rows.bit_length() - 1)]
    z = z.reshape(nlimbs, a_rows * cepb, n0, b_lanes).transpose(0, 2, 3, 1)
    z = z[:, :, _bitrev_order(b_lanes.bit_length() - 1)]
    return z.reshape(nlimbs, -1).T


@functools.partial(
    jax.jit, static_argnames=("vtype", "num_blocks", "party")
)
def _leaf_stage_at(seeds, control, vc_dev, block_indices, vtype, num_blocks, party):
    """Leaf values at specific block indices (point evaluation).

    vc_dev leaves may have a leading per-key axis matching n, or be shared.
    Returns a value pytree with batch shape [n].
    """
    blocks = _value_hash(seeds, num_blocks)
    values = vtype.dev_from_value_blocks(blocks)  # [n, epb, ...]
    picked = vtype.dev_take_element(values, block_indices)  # [n, ...]
    vc_picked = vtype.dev_take_element(vc_dev, block_indices)
    mask = control != 0
    corrected = vtype.dev_where(
        mask, vtype.dev_add(picked, vc_picked), picked
    )
    if party == 1:
        corrected = vtype.dev_neg(corrected)
    return corrected


# ---------------------------------------------------------------------------
# The DPF itself
# ---------------------------------------------------------------------------


class DistributedPointFunction:
    """Incremental DPF with hierarchical evaluation.

    Create with `create` (single hierarchy level) or `create_incremental`.
    """

    def __init__(self, parameters: Sequence[DpfParameters]):
        parameters = list(parameters)
        if not parameters:
            raise ValueError("parameters must not be empty")
        resolved = []
        prev_lds = 0
        for i, p in enumerate(parameters):
            if p.log_domain_size < 0 or p.log_domain_size > 128:
                raise ValueError("log_domain_size must be in [0, 128]")
            if i > 0 and p.log_domain_size <= prev_lds:
                raise ValueError("log_domain_size must be strictly ascending")
            prev_lds = p.log_domain_size
            sec = p.security_parameter
            if sec != 0.0 and not (0 <= sec <= 128):
                raise ValueError("security_parameter must be in [0, 128]")
            if sec == 0.0:
                sec = 40 + p.log_domain_size
            resolved.append(
                DpfParameters(p.log_domain_size, p.value_type, sec)
            )
        self.parameters = resolved

        # Hierarchy -> tree level mapping (`proto_validator.cc:127-153`):
        # packed value types shorten the tree by up to 7 levels.
        self._hierarchy_to_tree: List[int] = []
        self._tree_to_hierarchy: Dict[int, int] = {}
        self._bits_needed: List[int] = []
        tree_levels_needed = 0
        for i, p in enumerate(self.parameters):
            bits = p.value_type.bits_needed(p.security_parameter)
            self._bits_needed.append(bits)
            log_bits = max(0, (bits - 1).bit_length())  # ceil(log2(bits))
            tree_level = max(
                tree_levels_needed, p.log_domain_size - 7 + min(log_bits, 7)
            )
            self._tree_to_hierarchy[tree_level] = i
            self._hierarchy_to_tree.append(tree_level)
            tree_levels_needed = max(tree_levels_needed, tree_level + 1)
        self._tree_levels_needed = tree_levels_needed
        self._blocks_needed = [(b + 127) // 128 for b in self._bits_needed]

    # -- constructors -------------------------------------------------------

    @classmethod
    def create(cls, parameters: DpfParameters) -> "DistributedPointFunction":
        return cls([parameters])

    @classmethod
    def create_incremental(
        cls, parameters: Sequence[DpfParameters]
    ) -> "DistributedPointFunction":
        return cls(parameters)

    # -- key generation (host) ---------------------------------------------

    def generate_keys(self, alpha: int, beta) -> Tuple[DpfKey, DpfKey]:
        """Single-hierarchy-level key generation."""
        if len(self.parameters) != 1:
            raise ValueError(
                "generate_keys requires exactly one hierarchy level; use "
                "generate_keys_incremental"
            )
        return self.generate_keys_incremental(alpha, [beta])

    def generate_keys_batch(
        self, alphas: Sequence[int], betas: Sequence, _root_seeds=None
    ) -> Tuple[List[DpfKey], List[DpfKey]]:
        """Generate key pairs for many (alpha, beta) points at once.

        The `GenerateNext` recurrence (`distributed_point_function.cc:
        121-222`) is sequential in tree depth but embarrassingly parallel
        across keys; the per-key Python loop costs ~ms/key, which dominates
        client time at the 1024-query benchmark config. This path runs all
        keys' levels in lockstep with the batched numpy AES oracle — one
        AES call per (level, PRG key) for the whole batch.

        Vectorized for the dense-PIR key shape (single hierarchy level,
        128-bit XOR value type, domain <= 2^63); other shapes fall back to
        per-key `generate_keys_incremental`.
        """
        from .value_types import XorType

        n = len(alphas)
        if len(betas) != n:
            raise ValueError("alphas and betas must have the same length")
        vt = self.parameters[0].value_type
        lds = self.parameters[-1].log_domain_size
        fast = (
            len(self.parameters) == 1
            and isinstance(vt, XorType)
            and vt.bits == 128
            and self._blocks_needed[0] == 1
            and lds <= 63
        )
        if not fast:
            pairs = [
                self.generate_keys_incremental(a, [b])
                for a, b in zip(alphas, betas)
            ]
            return [p[0] for p in pairs], [p[1] for p in pairs]

        for a in alphas:
            if not isinstance(a, (int, np.integer)):
                raise TypeError(f"alpha must be an integer, got {type(a)}")
            if not (0 <= a < (1 << lds)):
                raise ValueError("alpha out of domain range")
        alphas_np = np.asarray([int(a) for a in alphas], dtype=np.uint64)
        for b in betas:
            vt.validate(b)

        # Root seeds: cryptographically random, both parties (injectable
        # for the native-vs-numpy differential tests).
        if _root_seeds is not None:
            raw = np.ascontiguousarray(_root_seeds, dtype=np.uint32)
        else:
            raw = np.frombuffer(
                secrets.token_bytes(16 * 2 * n), dtype="<u4"
            ).reshape(2, n, 4).copy()
        num_cw = self._tree_levels_needed - 1
        beta_limbs = np.zeros((n, 4), dtype=np.uint32)
        for i, b in enumerate(betas):
            beta_limbs[i] = aes.u128_to_limbs(int(b))

        engine = self._keygen_engine()
        if engine == "native":
            from . import native as native_mod

            cw8, ctrl8, vc8 = native_mod.keygen_batch_dense(
                np.ascontiguousarray(raw).view(np.uint8).reshape(2, n, 16),
                alphas_np,
                np.ascontiguousarray(beta_limbs).view(np.uint8),
                num_cw,
            )
            cw_seeds = np.ascontiguousarray(cw8).view("<u4").reshape(
                num_cw, n, 4
            ).astype(np.uint32)
            cw_lefts = ctrl8[..., 0].astype(np.uint32)
            cw_rights = ctrl8[..., 1].astype(np.uint32)
            vc = np.ascontiguousarray(vc8).view("<u4").reshape(n, 4).astype(
                np.uint32
            )
            return self._assemble_dense_keys(
                raw, cw_seeds, cw_lefts, cw_rights, vc
            )

        seeds = [raw[0], raw[1]]  # per party: uint32[n, 4]
        control = [
            np.zeros(n, dtype=np.uint32),
            np.ones(n, dtype=np.uint32),
        ]
        cw_seeds = np.zeros((num_cw, n, 4), dtype=np.uint32)
        cw_lefts = np.zeros((num_cw, n), dtype=np.uint32)
        cw_rights = np.zeros((num_cw, n), dtype=np.uint32)

        for tree_level in range(1, self._tree_levels_needed):
            both = np.concatenate(seeds, axis=0)  # [2n, 4]
            l = aes.mmo_hash_np(fixed_keys.RK_LEFT, both)
            r = aes.mmo_hash_np(fixed_keys.RK_RIGHT, both)
            t_l = l[:, 0] & 1
            t_r = r[:, 0] & 1
            l[:, 0] &= 0xFFFFFFFE
            r[:, 0] &= 0xFFFFFFFE
            l0, l1 = l[:n], l[n:]
            r0, r1 = r[:n], r[n:]

            bit_pos = lds - tree_level
            bit = ((alphas_np >> np.uint64(bit_pos)) & np.uint64(1)).astype(
                np.uint32
            )
            bitc = bit[:, None]
            # lose = 1 - bit: the branch alpha does NOT take.
            cw_seed = np.where(bitc != 0, l0 ^ l1, r0 ^ r1)
            cw_left = t_l[:n] ^ t_l[n:] ^ bit ^ 1
            cw_right = t_r[:n] ^ t_r[n:] ^ bit
            cw_keep = np.where(bit != 0, cw_right, cw_left)

            new_seeds = []
            new_control = []
            for b in (0, 1):
                keep_seed = np.where(bitc != 0, (r0, r1)[b], (l0, l1)[b])
                keep_t = np.where(bit != 0, t_r[b * n : (b + 1) * n],
                                  t_l[b * n : (b + 1) * n])
                new_seeds.append(
                    keep_seed ^ (control[b][:, None] * cw_seed)
                )
                new_control.append(keep_t ^ (control[b] & cw_keep))
            seeds, control = new_seeds, new_control
            lvl = tree_level - 1
            cw_seeds[lvl], cw_lefts[lvl], cw_rights[lvl] = (
                cw_seed, cw_left, cw_right,
            )

        # Last-level value correction: H_value(s1) - H_value(s0) + beta at
        # alpha's block position; for 128-bit XOR shares both group ops are
        # XOR and party negation is the identity
        # (`ComputeValueCorrection`, `distributed_point_function.cc:81-117`).
        ha = aes.mmo_hash_np(fixed_keys.RK_VALUE, seeds[0])
        hb = aes.mmo_hash_np(fixed_keys.RK_VALUE, seeds[1])
        vc = ha ^ hb ^ beta_limbs
        return self._assemble_dense_keys(
            raw, cw_seeds, cw_lefts, cw_rights, vc
        )

    def _keygen_engine(self) -> str:
        """'native' (C++ AES-NI batch keygen) or 'numpy'.

        DPF_NATIVE_KEYGEN=0 forces numpy; =1 requires native (raising if
        the library cannot load); default tries native, falls back quietly.
        """
        mode = os.environ.get("DPF_NATIVE_KEYGEN", "auto")
        if mode == "0":
            return "numpy"
        try:
            from . import native as native_mod

            native_mod.get_lib()
            return "native"
        except Exception:
            if mode == "1":
                raise
            return "numpy"

    def _assemble_dense_keys(self, raw, cw_seeds, cw_lefts, cw_rights, vc):
        n = raw.shape[1]
        num_cw = cw_seeds.shape[0]
        keys0: List[DpfKey] = []
        keys1: List[DpfKey] = []
        for i in range(n):
            cws = [
                CorrectionWord(
                    seed=aes.limbs_to_u128(cw_seeds[lvl, i]),
                    control_left=bool(cw_lefts[lvl, i]),
                    control_right=bool(cw_rights[lvl, i]),
                    value_correction=None,
                )
                for lvl in range(num_cw)
            ]
            last_vc = [aes.limbs_to_u128(vc[i])]
            keys0.append(
                DpfKey(
                    seed=aes.limbs_to_u128(raw[0, i]),
                    party=0,
                    correction_words=cws,
                    last_level_value_correction=last_vc,
                )
            )
            keys1.append(
                DpfKey(
                    seed=aes.limbs_to_u128(raw[1, i]),
                    party=1,
                    correction_words=[
                        dataclasses.replace(cw) for cw in cws
                    ],
                    last_level_value_correction=list(last_vc),
                )
            )
        return keys0, keys1

    def generate_keys_incremental(
        self, alpha: int, betas: Sequence
    ) -> Tuple[DpfKey, DpfKey]:
        """Generate the two parties' keys for point alpha with values betas.

        Follows the recurrence of `GenerateKeysIncremental` / `GenerateNext`
        (`distributed_point_function.cc:642-707, 121-222`), including the
        PRG-evaluation optimization (value correction computed from the
        *pre-expansion* seeds at each output level).
        """
        if len(betas) != len(self.parameters):
            raise ValueError("betas must have one entry per hierarchy level")
        for p, b in zip(self.parameters, betas):
            p.value_type.validate(b)
        last_lds = self.parameters[-1].log_domain_size
        if not (0 <= alpha < (1 << last_lds)):
            raise ValueError("alpha out of domain range")

        root_seeds = [secrets.randbits(128), secrets.randbits(128)]
        seeds = list(root_seeds)
        control = [0, 1]
        correction_words: List[CorrectionWord] = []

        for tree_level in range(1, self._tree_levels_needed):
            value_correction = None
            if (tree_level - 1) in self._tree_to_hierarchy:
                hl = self._tree_to_hierarchy[tree_level - 1]
                value_correction = self._compute_value_correction(
                    hl, seeds, alpha, betas[hl], invert=bool(control[1])
                )
            # Expand both parties' seeds left and right.
            l0, l1 = _mmo_host(fixed_keys.RK_LEFT, seeds)
            r0, r1 = _mmo_host(fixed_keys.RK_RIGHT, seeds)
            t = [[l0 & 1, l1 & 1], [r0 & 1, r1 & 1]]  # [branch][party]
            l0 &= ~1
            l1 &= ~1
            r0 &= ~1
            r1 &= ~1
            expanded = [[l0, l1], [r0, r1]]

            bit_pos = last_lds - tree_level
            current_bit = (alpha >> bit_pos) & 1 if bit_pos < 128 else 0
            keep, lose = current_bit, 1 - current_bit

            cw_seed = expanded[lose][0] ^ expanded[lose][1]
            cw_control = [
                t[0][0] ^ t[0][1] ^ current_bit ^ 1,  # left
                t[1][0] ^ t[1][1] ^ current_bit,  # right
            ]
            new_seeds = [
                expanded[keep][b] ^ (cw_seed if control[b] else 0)
                for b in (0, 1)
            ]
            new_control = [
                t[keep][b] ^ (control[b] & cw_control[keep]) for b in (0, 1)
            ]
            correction_words.append(
                CorrectionWord(
                    seed=cw_seed,
                    control_left=bool(cw_control[0]),
                    control_right=bool(cw_control[1]),
                    value_correction=value_correction,
                )
            )
            seeds, control = new_seeds, new_control

        last_vc = self._compute_value_correction(
            len(self.parameters) - 1,
            seeds,
            alpha,
            betas[-1],
            invert=bool(control[1]),
        )
        key0 = DpfKey(
            seed=root_seeds[0],
            party=0,
            correction_words=correction_words,
            last_level_value_correction=list(last_vc),
        )
        key1 = DpfKey(
            seed=root_seeds[1],
            party=1,
            correction_words=[
                dataclasses.replace(
                    cw,
                    value_correction=(
                        None
                        if cw.value_correction is None
                        else list(cw.value_correction)
                    ),
                )
                for cw in correction_words
            ],
            last_level_value_correction=list(last_vc),
        )
        return key0, key1

    # -- evaluation (device) ------------------------------------------------

    def create_evaluation_context(self, key: DpfKey) -> EvaluationContext:
        self._validate_key(key)
        return EvaluationContext(key=key)

    def _validate_key(self, key: DpfKey) -> None:
        if key.party not in (0, 1):
            raise ValueError("key.party must be 0 or 1")
        if len(key.correction_words) != self._tree_levels_needed - 1:
            raise ValueError(
                f"key has {len(key.correction_words)} correction words, "
                f"expected {self._tree_levels_needed - 1}"
            )

    def _stage_correction_words(self, key: DpfKey, start: int, stop: int):
        """Correction words [start, stop) as device-ready numpy arrays."""
        n = stop - start
        cw_seeds = np.zeros((n, 4), dtype=np.uint32)
        cw_left = np.zeros((n,), dtype=np.uint32)
        cw_right = np.zeros((n,), dtype=np.uint32)
        for i, cw in enumerate(key.correction_words[start:stop]):
            cw_seeds[i] = aes.u128_to_limbs(cw.seed)
            cw_left[i] = cw.control_left
            cw_right[i] = cw.control_right
        return cw_seeds, cw_left, cw_right

    def _value_correction_host(self, key: DpfKey, hierarchy_level: int):
        """The hierarchy level's value-correction host values (length epb)."""
        if hierarchy_level < len(self.parameters) - 1:
            return key.correction_words[
                self._hierarchy_to_tree[hierarchy_level]
            ].value_correction
        return key.last_level_value_correction

    def stage_key_batch(self, keys: Sequence[DpfKey]) -> StagedKeyBatch:
        """Stage a batch of keys to device arrays in one pass.

        One host loop over the keys assembles numpy arrays (seeds,
        correction words for every tree level, value corrections for every
        hierarchy level); when every block is uint32 — the common case,
        since `host_const` emits uint32 limb arrays exactly so batch
        staging can do this — they concatenate into ONE flat transfer
        and slice back apart on device (a single h2d copy in the
        TransferLedger instead of 5 + one per value-correction leaf).
        The result can be passed to `evaluate_and_apply` any number of
        times — the staging cost is paid once per batch, not per
        evaluation.
        """
        n = len(keys)
        num_cw = self._tree_levels_needed - 1
        seeds_np = np.zeros((n, 4), dtype=np.uint32)
        parties_np = np.zeros((n,), dtype=np.uint32)
        cw_seeds = np.zeros((num_cw, n, 4), dtype=np.uint32)
        cw_left = np.zeros((num_cw, n), dtype=np.uint32)
        cw_right = np.zeros((num_cw, n), dtype=np.uint32)
        for j, key in enumerate(keys):
            self._validate_key(key)
            seeds_np[j] = aes.u128_to_limbs(key.seed)
            parties_np[j] = key.party
            for i, cw in enumerate(key.correction_words):
                cw_seeds[i, j] = aes.u128_to_limbs(cw.seed)
                cw_left[i, j] = cw.control_left
                cw_right[i, j] = cw.control_right
        stack0 = functools.partial(np.stack, axis=0)
        stacked_levels = []  # (treedef, host leaves) per hierarchy level
        for hl, p in enumerate(self.parameters):
            vt = p.value_type
            per_key = [
                jax.tree_util.tree_map(
                    lambda *xs: stack0(xs),
                    *[
                        vt.host_const(v)
                        for v in self._value_correction_host(key, hl)
                    ],
                )
                for key in keys
            ]
            stacked = jax.tree_util.tree_map(
                lambda *xs: stack0(xs), *per_key
            )
            leaves, treedef = jax.tree_util.tree_flatten(stacked)
            stacked_levels.append((treedef, leaves))
        ledger = default_telemetry().transfers
        blocks = [seeds_np, parties_np, cw_seeds, cw_left, cw_right]
        blocks += [lv for _, leaves in stacked_levels for lv in leaves]
        if all(
            isinstance(b, np.ndarray) and b.dtype == np.uint32
            for b in blocks
        ):
            flat = np.concatenate([b.ravel() for b in blocks])
            dev = ledger.device_put(flat, phase="key_staging")
            staged = []
            offset = 0
            for b in blocks:
                staged.append(dev[offset:offset + b.size].reshape(b.shape))
                offset += b.size
        else:
            # A non-uint32 host_const (a custom value type) keeps its
            # own transfer; still counted, just not packed.
            staged = [
                ledger.device_put(b, phase="key_staging") for b in blocks
            ]
        leaf_iter = iter(staged[5:])
        value_corrections = [
            jax.tree_util.tree_unflatten(
                treedef, [next(leaf_iter) for _ in leaves]
            )
            for treedef, leaves in stacked_levels
        ]
        return StagedKeyBatch(
            n=n,
            seeds=staged[0],
            parties=staged[1],
            cw_seeds=staged[2],
            cw_left=staged[3],
            cw_right=staged[4],
            value_corrections=value_corrections,
        )

    def _stage_value_correction(self, key: DpfKey, hierarchy_level: int):
        """Device pytree [epb] of the value correction at a hierarchy level.

        Stored in the correction word at the level's tree level, except for
        the last hierarchy level which uses the dedicated key field
        (`distributed_point_function.h:820-834`).
        """
        if hierarchy_level < len(self.parameters) - 1:
            vc = key.correction_words[
                self._hierarchy_to_tree[hierarchy_level]
            ].value_correction
        else:
            vc = key.last_level_value_correction
        vt = self.parameters[hierarchy_level].value_type
        parts = [vt.dev_const(v, ()) for v in vc]
        return jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0), *parts
        )

    def _expand(self, seeds: jnp.ndarray, control: jnp.ndarray,
                key: DpfKey, start: int, stop: int,
                hash_leaves: bool = False, staged_cw=None):
        """Expand seeds from tree level `start` to `stop` (width-doubling).

        All levels run in ONE jitted program (specialized per level count
        via `_expand_levels_fn`): a per-level Python loop of `_expand_level`
        jits would pay one dispatch per level and a fresh compile per
        distinct width. With `hash_leaves` the returned seeds are already
        value-hashed (fused `HashExpandedSeeds`; single-block value types
        only — the leaf stage then skips its own hash). `staged_cw` is
        `_device_correction_words(key, start, stop)` when the caller has
        already placed them.
        """
        if stop == start:
            if hash_leaves:
                return (
                    aes.mmo_hash(fixed_keys.RK_VALUE, seeds), control
                )
            return seeds, control
        if staged_cw is None:
            staged_cw = self._device_correction_words(key, start, stop)
        return _expand_levels_fn(stop - start, hash_leaves=hash_leaves)(
            seeds, control, *staged_cw
        )

    def _device_correction_words(self, key: DpfKey, start: int, stop: int):
        """Levels [start, stop) of `key`'s correction words, on device."""
        return tuple(
            jnp.asarray(x)
            for x in self._stage_correction_words(key, start, stop)
        )

    def _walk_paths(self, seeds, control, paths_np, key_or_keys, start: int,
                    stop: int, rightshift: int):
        """Point-evaluate: walk paths from tree level start to stop.

        `key_or_keys` is one key (shared correction words) or a list of
        per-seed keys (multi-key batch mode). `paths_np` is uint32[n, 4].
        The path bit for level j in [start, stop) is bit
        (stop - 1 - j + rightshift) of the path, mirroring
        `EvaluateSeedsNoHwy` (`evaluate_prg_hwy.cc:591-633`).
        """
        num_levels = stop - start
        if num_levels == 0:
            return seeds, control
        if isinstance(key_or_keys, DpfKey):
            cw_seeds, cw_left, cw_right = self._stage_correction_words(
                key_or_keys, start, stop
            )
            cw_seeds = cw_seeds[:, None, :]  # [L, 1, 4]
            cw_left = cw_left[:, None]
            cw_right = cw_right[:, None]
        else:
            staged = [
                self._stage_correction_words(k, start, stop)
                for k in key_or_keys
            ]
            cw_seeds = np.stack([s[0] for s in staged], axis=1)  # [L, n, 4]
            cw_left = np.stack([s[1] for s in staged], axis=1)
            cw_right = np.stack([s[2] for s in staged], axis=1)
        bit_indices = np.array(
            [num_levels - 1 - j + rightshift for j in range(num_levels)],
            dtype=np.int32,
        )
        return _eval_paths(
            seeds,
            control,
            jnp.asarray(paths_np),
            jnp.asarray(cw_seeds),
            jnp.asarray(cw_left),
            jnp.asarray(cw_right),
            jnp.asarray(bit_indices),
        )

    def _leaf_values(self, seeds, control, key: DpfKey,
                     hierarchy_level: int, pre_hashed: bool = False):
        """Full-expansion leaf values, flattened to domain order."""
        vt = self.parameters[hierarchy_level].value_type
        cepb = 1 << (
            self.parameters[hierarchy_level].log_domain_size
            - self._hierarchy_to_tree[hierarchy_level]
        )
        vc_dev = self._stage_value_correction(key, hierarchy_level)
        values = _leaf_stage(
            seeds,
            control,
            vc_dev,
            self.parameters[hierarchy_level].value_type,
            cepb,
            self._blocks_needed[hierarchy_level],
            key.party,
            pre_hashed=pre_hashed,
        )
        # Flatten [n, cepb, ...] -> [n * cepb, ...] (domain order).
        return jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), values
        )

    def _expand_leaf_values(self, seeds, control, key: DpfKey, start: int,
                            stop: int, hierarchy_level: int,
                            staged_cw=None):
        """Expand seeds from tree level `start` to `stop` and return the
        leaf values flattened to domain order. Integer values of one
        block take the direct exit and the lane-dense leaf stage where
        the expansion plan allows it (`_leaf_exit`); every other value
        type, and the limb program, take the gather exit and
        `_leaf_values`. The `dpf.expand` span names the exit taken."""
        if stop - start > 62:
            raise ValueError(
                "trying to expand more than 62 tree levels at once; insert "
                "intermediate hierarchy levels"
            )
        vt = self.parameters[hierarchy_level].value_type
        fuse = self._blocks_needed[hierarchy_level] == 1
        exit = "gather"
        if fuse and isinstance(vt, IntType):
            exit = _leaf_exit(seeds.shape[0], stop - start)
        with tracing.span("dpf.expand", record=False, exit=exit):
            if exit == "gather":
                seeds, control = self._expand(
                    seeds, control, key, start, stop, hash_leaves=fuse,
                    staged_cw=staged_cw,
                )
            else:
                if staged_cw is None:
                    staged_cw = self._device_correction_words(
                        key, start, stop
                    )
                limbs, ctrl = _expand_levels_fn(
                    stop - start, hash_leaves=True, exit=exit
                )(seeds, control, *staged_cw)
        with tracing.span("dpf.leaf_values", record=False):
            if exit == "gather":
                return self._leaf_values(
                    seeds, control, key, hierarchy_level, pre_hashed=fuse
                )
            cepb = 1 << (
                self.parameters[hierarchy_level].log_domain_size
                - self._hierarchy_to_tree[hierarchy_level]
            )
            vc = np.stack([
                vt.host_const(v)
                for v in self._value_correction_host(key, hierarchy_level)
            ][:cepb])
            return _leaf_stage_lanes(
                limbs, ctrl, vc, bits=vt.bits, cepb=cepb, party=key.party
            )

    def evaluate_next(self, prefixes: Sequence[int], ctx: EvaluationContext):
        """Evaluate the hierarchy level after `ctx.previous_hierarchy_level`.

        On the first call `prefixes` must be empty (full expansion of level
        0); on later calls it holds domain indices at the previous hierarchy
        level whose subtrees to expand. Mirrors `EvaluateNext`
        (`distributed_point_function.h:319-333`).
        """
        with tracing.span("dpf.evaluate_next", record=False):
            return self.evaluate_until(
                ctx.previous_hierarchy_level + 1, prefixes, ctx
            )

    def evaluate_until(self, hierarchy_level: int, prefixes: Sequence[int],
                       ctx: EvaluationContext):
        """Hierarchical evaluation up to `hierarchy_level` (`EvaluateUntil`).

        Returns a value pytree with leading dim
        `len(prefixes) * 2^(lds_level - lds_prev)` (or the full expansion on
        the first call), in domain order grouped by prefix.
        """
        key = ctx.key
        self._validate_key(key)
        if hierarchy_level < 0 or hierarchy_level >= len(self.parameters):
            raise ValueError("hierarchy_level out of range")
        if hierarchy_level <= ctx.previous_hierarchy_level:
            raise ValueError(
                "hierarchy_level must be greater than "
                "ctx.previous_hierarchy_level"
            )
        if (ctx.previous_hierarchy_level < 0) != (len(prefixes) == 0):
            raise ValueError(
                "prefixes must be empty iff this is the first call with ctx"
            )
        prev_hl = ctx.previous_hierarchy_level
        prev_lds = (
            self.parameters[prev_hl].log_domain_size if prev_hl >= 0 else 0
        )
        lds = self.parameters[hierarchy_level].log_domain_size
        if lds - prev_lds > 62:
            raise ValueError(
                "output size would exceed 2^62; evaluate fewer hierarchy "
                "levels at once"
            )
        for prefix in prefixes:
            if not (0 <= prefix < (1 << prev_lds)):
                raise ValueError(f"prefix {prefix} out of range")

        stop_level = self._hierarchy_to_tree[hierarchy_level]
        if not prefixes:
            # Host spans of a full-domain evaluation (dispatch only: the
            # device runs the programs after these return).
            with tracing.span("dpf.stage_key", record=False):
                seeds = jnp.asarray(aes.u128_to_limbs(key.seed))[None, :]
                control = jnp.asarray(
                    np.array([key.party], dtype=np.uint32)
                )
                staged_cw = (
                    self._device_correction_words(key, 0, stop_level)
                    if stop_level > 0 else None
                )
            out = self._expand_leaf_values(
                seeds, control, key, 0, stop_level, hierarchy_level,
                staged_cw=staged_cw,
            )
            ctx.previous_hierarchy_level = hierarchy_level
            return out

        # Split prefixes into unique tree indices + block indices
        # (packed value types: several prefixes share a tree node).
        tree_indices: List[int] = []
        tree_pos: Dict[int, int] = {}
        prefix_map: List[Tuple[int, int]] = []
        for prefix in prefixes:
            ti = self._domain_to_tree_index(prefix, prev_hl)
            bi = self._domain_to_block_index(prefix, prev_hl)
            if ti not in tree_pos:
                tree_pos[ti] = len(tree_indices)
                tree_indices.append(ti)
            prefix_map.append((tree_pos[ti], bi))

        update_ctx = hierarchy_level < len(self.parameters) - 1
        seeds, control = self._compute_partial_evaluations(
            tree_indices, prev_hl, update_ctx, ctx
        )
        start_level = self._hierarchy_to_tree[prev_hl]
        values = self._expand_leaf_values(
            seeds, control, key, start_level, stop_level, hierarchy_level
        )

        # Select the per-prefix output spans.
        outputs_per_prefix = 1 << (lds - prev_lds)
        cepb = 1 << (lds - stop_level)
        blocks_per_tree_prefix = (1 << (stop_level - start_level))
        span = blocks_per_tree_prefix * cepb
        idx = np.empty((len(prefixes), outputs_per_prefix), dtype=np.int64)
        base = np.arange(outputs_per_prefix, dtype=np.int64)
        for i, (tp, bi) in enumerate(prefix_map):
            idx[i] = tp * span + bi * outputs_per_prefix + base
        flat_idx = jnp.asarray(idx.reshape(-1))
        out = jax.tree_util.tree_map(
            lambda x: jnp.take(x, flat_idx, axis=0), values
        )
        ctx.previous_hierarchy_level = hierarchy_level
        return out

    def _compute_partial_evaluations(
        self, tree_indices: Sequence[int], hierarchy_level: int,
        update_ctx: bool, ctx: EvaluationContext,
    ):
        """Seeds/control bits for `tree_indices` at hierarchy_level's tree
        level, resuming from `ctx.partial_evaluations` when possible
        (`ComputePartialEvaluations`, `distributed_point_function.cc:374-476`).
        """
        key = ctx.key
        stop_level = self._hierarchy_to_tree[hierarchy_level]
        start_level = self._hierarchy_to_tree[ctx.partial_evaluations_level]
        n = len(tree_indices)
        # Bucket the batch to the next power of two: distinct prefix counts
        # would otherwise each compile a fresh XLA program (padding rows
        # carry zero seeds/paths and are ignored downstream — real rows are
        # always the leading ones).
        n_pad = _next_pow2(n)
        paths_np = np.zeros((n_pad, 4), dtype=np.uint32)
        paths_np[:n] = np.stack(
            [aes.u128_to_limbs(t) for t in tree_indices]
        ).astype(np.uint32)

        pe = ctx.partial_evaluations
        if isinstance(pe, dict) and pe:
            pe = PartialEvaluations.from_dict(pe)
        seeds_np = np.zeros((n_pad, 4), dtype=np.uint32)
        control_np = np.zeros((n_pad,), dtype=np.uint32)
        if isinstance(pe, PartialEvaluations) and start_level <= stop_level:
            shift = stop_level - start_level
            if shift == 0:
                prev = list(tree_indices)
            elif shift >= 128:
                prev = [0] * n
            else:
                prev = [ti >> shift for ti in tree_indices]
            try:
                seeds_np[:n], control_np[:n] = pe.lookup(prev)
            except ValueError as e:
                raise ValueError(
                    f"{e} at hierarchy level {hierarchy_level}"
                ) from None
        else:
            seeds_np[:n] = aes.u128_to_limbs(key.seed)
            control_np[:n] = key.party
            start_level = 0

        seeds, control = self._walk_paths(
            jnp.asarray(seeds_np),
            jnp.asarray(control_np),
            paths_np,
            key,
            start_level,
            stop_level,
            rightshift=0,
        )

        ctx.partial_evaluations = {}
        if update_ctx:
            ctx.partial_evaluations = PartialEvaluations.build(
                list(tree_indices),
                np.asarray(seeds)[:n],
                np.asarray(control)[:n],
            )
        ctx.partial_evaluations_level = hierarchy_level
        return seeds, control

    def evaluate_at(self, key: DpfKey, hierarchy_level: int,
                    evaluation_points: Sequence[int],
                    ctx: Optional[EvaluationContext] = None):
        """Evaluate `key` at the given points of hierarchy level's domain.

        Returns a value pytree with leading dim len(evaluation_points).
        Mirrors `EvaluateAt` (`distributed_point_function.h:913-1070`).
        """
        if ctx is not None and ctx.key is not key:
            raise ValueError("key and ctx.key must be the same object")
        self._validate_key(key)
        if not (0 <= hierarchy_level < len(self.parameters)):
            raise ValueError("hierarchy_level out of range")
        lds = self.parameters[hierarchy_level].log_domain_size
        for pt in evaluation_points:
            if not (0 <= pt < (1 << lds)):
                raise ValueError(f"evaluation point {pt} out of range")
        n = len(evaluation_points)
        if n == 0:
            vt = self.parameters[hierarchy_level].value_type
            return vt.dev_zeros((0,))

        tree_indices = [
            self._domain_to_tree_index(pt, hierarchy_level)
            for pt in evaluation_points
        ]
        stop_level = self._hierarchy_to_tree[hierarchy_level]
        # Bucketed batch (see _compute_partial_evaluations): pad the point
        # count to a power of two so point-eval shapes recur.
        n_pad = _next_pow2(n)

        if ctx is None:
            paths_np = np.zeros((n_pad, 4), dtype=np.uint32)
            paths_np[:n] = np.stack(
                [aes.u128_to_limbs(t) for t in tree_indices]
            ).astype(np.uint32)
            seeds_np = np.zeros((n_pad, 4), dtype=np.uint32)
            seeds_np[:n] = aes.u128_to_limbs(key.seed)
            control_np = np.zeros((n_pad,), dtype=np.uint32)
            control_np[:n] = key.party
            seeds, control = self._walk_paths(
                jnp.asarray(seeds_np),
                jnp.asarray(control_np),
                paths_np,
                key,
                0,
                stop_level,
                rightshift=0,
            )
        else:
            seeds, control = self._compute_partial_evaluations(
                tree_indices, hierarchy_level, True, ctx
            )
            ctx.previous_hierarchy_level = hierarchy_level

        vc_dev = self._stage_value_correction(key, hierarchy_level)
        block_indices_np = np.zeros((n_pad,), dtype=np.int32)
        block_indices_np[:n] = [
            self._domain_to_block_index(pt, hierarchy_level)
            for pt in evaluation_points
        ]
        vc_dev = jax.tree_util.tree_map(lambda x: x[None], vc_dev)
        out = _leaf_stage_at(
            seeds,
            control,
            vc_dev,
            jnp.asarray(block_indices_np),
            self.parameters[hierarchy_level].value_type,
            self._blocks_needed[hierarchy_level],
            key.party,
        )
        if n_pad == n:
            return out
        return jax.tree_util.tree_map(lambda x: x[:n], out)

    def evaluate_prefixes_batch(
        self,
        staged: StagedKeyBatch,
        hierarchy_level: int,
        prefixes: Sequence[int],
        cuts: Optional[BatchCutState] = None,
        *,
        donate_cuts: bool = False,
    ):
        """Evaluate EVERY staged key at EVERY prefix of one hierarchy
        level, resuming from cached cut states — the batched per-level
        engine of the heavy-hitters sweep (usable by any workload that
        needs a fused `[num_keys, num_prefixes]` evaluation).

        `prefixes` are strictly ascending domain indices at
        `hierarchy_level`. With `cuts` (a `BatchCutState` from an
        earlier level) each lane's walk starts from the cached seed of
        the prefix's ancestor at `cuts.hierarchy_level` — every
        ancestor must be present in `cuts.prefixes` — so only
        `tree(level) - tree(cuts.level)` tree levels are hashed instead
        of the full root-to-level path. Without `cuts` the walk starts
        at the root.

        Lanes are laid out key-major (`lane = key * P_pad + prefix`)
        and run through the per-seed correction-word mode of
        `_eval_paths` in ONE fused device program; the prefix axis is
        padded to a power of two so frontier widths recur in the jit
        cache. Returns `(values, new_cuts)`: `values` is the hierarchy
        level's value pytree with batch shape
        `[num_keys, len(prefixes)]` (party negation applied per key),
        and `new_cuts` the `BatchCutState` at `hierarchy_level` for the
        next level's resume.

        `donate_cuts=True` asserts this call is the LAST read of
        `cuts`: its seed/control device buffers are donated into the
        resume gather so the new frontier can reuse their HBM. The
        caller must discard `cuts` afterwards (on TPU its buffers are
        deleted); opt-in because a chunked level resumes from one cut
        state several times.
        """
        num_keys = staged.n
        num_prefixes = len(prefixes)
        if num_prefixes == 0:
            raise ValueError("prefixes must not be empty")
        if not (0 <= hierarchy_level < len(self.parameters)):
            raise ValueError("hierarchy_level out of range")
        lds = self.parameters[hierarchy_level].log_domain_size
        last = -1
        for p in prefixes:
            if not (0 <= p < (1 << lds)):
                raise ValueError(f"prefix {p} out of range")
            if p <= last:
                raise ValueError(
                    "prefixes must be strictly ascending"
                )
            last = p

        stop_level = self._hierarchy_to_tree[hierarchy_level]
        tree_indices = [
            self._domain_to_tree_index(p, hierarchy_level)
            for p in prefixes
        ]
        block_indices = [
            self._domain_to_block_index(p, hierarchy_level)
            for p in prefixes
        ]

        p_pad = _next_pow2(num_prefixes)
        paths_np = np.zeros((p_pad, 4), dtype=np.uint32)
        paths_np[:num_prefixes] = np.stack(
            [aes.u128_to_limbs(t) for t in tree_indices]
        ).astype(np.uint32)

        if cuts is None:
            start_level = 0
            seeds = jnp.broadcast_to(
                staged.seeds[:, None, :], (num_keys, p_pad, 4)
            )
            control = jnp.broadcast_to(
                staged.parties[:, None], (num_keys, p_pad)
            )
        else:
            if cuts.hierarchy_level >= hierarchy_level:
                raise ValueError(
                    "cuts.hierarchy_level must precede hierarchy_level"
                )
            if cuts.num_keys != num_keys:
                raise ValueError("cuts/staged key-count mismatch")
            start_level = self._hierarchy_to_tree[cuts.hierarchy_level]
            prev_lds = self.parameters[cuts.hierarchy_level].log_domain_size
            shift = lds - prev_lds
            parents = [p >> shift for p in prefixes]
            pos_np = np.zeros((p_pad,), dtype=np.int64)
            pos_np[:num_prefixes] = cuts.positions(parents)
            pos = jnp.asarray(pos_np)
            if donate_cuts:
                seeds, control = _gather_cut_lanes_donated(
                    cuts.seeds, cuts.control, pos
                )
            else:
                seeds = jnp.take(cuts.seeds, pos, axis=1)
                control = jnp.take(cuts.control, pos, axis=1)

        n_lanes = num_keys * p_pad
        seeds = seeds.reshape(n_lanes, 4)
        control = control.reshape(n_lanes)
        paths = jnp.asarray(np.tile(paths_np, (num_keys, 1)))

        num_levels = stop_level - start_level
        if num_levels > 0:
            # Per-seed correction-word mode: lane (k, p) uses key k's
            # correction words, repeated across the prefix axis.
            cw_seeds = jnp.repeat(
                staged.cw_seeds[start_level:stop_level], p_pad, axis=1
            )
            cw_left = jnp.repeat(
                staged.cw_left[start_level:stop_level], p_pad, axis=1
            )
            cw_right = jnp.repeat(
                staged.cw_right[start_level:stop_level], p_pad, axis=1
            )
            bit_indices = np.array(
                [num_levels - 1 - j for j in range(num_levels)],
                dtype=np.int32,
            )
            seeds, control = _eval_paths(
                seeds, control, paths, cw_seeds, cw_left, cw_right,
                jnp.asarray(bit_indices),
            )

        vt = self.parameters[hierarchy_level].value_type
        vc = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, p_pad, axis=0),
            staged.value_corrections[hierarchy_level],
        )
        block_np = np.zeros((p_pad,), dtype=np.int32)
        block_np[:num_prefixes] = block_indices
        values = _leaf_stage_at(
            seeds,
            control,
            vc,
            jnp.asarray(np.tile(block_np, num_keys)),
            vt,
            self._blocks_needed[hierarchy_level],
            -1,  # party negation below, per key
        )
        parties = jnp.repeat(staged.parties, p_pad, axis=0)
        values = vt.dev_where(parties != 0, vt.dev_neg(values), values)
        values = jax.tree_util.tree_map(
            lambda x: x.reshape((num_keys, p_pad) + x.shape[1:])[
                :, :num_prefixes
            ],
            values,
        )

        wide = any(p > 0x7FFFFFFFFFFFFFFF for p in prefixes)
        new_cuts = BatchCutState(
            hierarchy_level=hierarchy_level,
            prefixes=np.array(
                list(prefixes), dtype=object if wide else np.uint64
            ),
            seeds=seeds.reshape(num_keys, p_pad, 4)[:, :num_prefixes],
            control=control.reshape(num_keys, p_pad)[:, :num_prefixes],
        )
        return values, new_cuts

    @property
    def _fused_accumulate_cache(self):
        if not hasattr(self, "_fused_acc_cache"):
            self._fused_acc_cache = {}
        return self._fused_acc_cache

    def evaluate_and_accumulate(self, staged: StagedKeyBatch,
                                evaluation_points: Sequence[int],
                                level_masks: np.ndarray,
                                evaluation_points_rightshift: int = 0):
        """Fused multi-key evaluation with a masked per-level accumulator.

        The `evaluate_and_apply` engine pays per-hierarchy-level Python
        dispatch (an `_eval_paths` jit call, a `_leaf_stage_at` jit call,
        and the host callback) — for the DCF benchmark shape (2^32
        domain = 32 levels, a few hundred keys) that overhead dominates
        wall-clock and the device arrays are tiny. Here the WHOLE
        multi-level walk + per-level value extraction + masked
        accumulation runs as one jitted program (levels unrolled;
        [n, 4] shapes are level-invariant so the program is reusable):
        `out[k] = sum over levels hl with level_masks[hl, k] of
        value_hl[k]`, exactly DCF's accumulator shape
        (`distributed_comparison_function.h:148-167`).

        Requires every hierarchy level to share one value type. Party
        negation is applied per level like `evaluate_and_apply`.
        """
        n = staged.n
        if n != len(evaluation_points):
            raise ValueError("keys and evaluation_points size mismatch")
        vt = self.parameters[0].value_type
        for p in self.parameters[1:]:
            if p.value_type != vt:
                raise ValueError(
                    "evaluate_and_accumulate requires a single value type "
                    "across hierarchy levels"
                )
        num_hl = len(self.parameters)
        if level_masks.shape != (num_hl, n):
            raise ValueError(
                f"level_masks must be [{num_hl}, {n}] booleans"
            )
        last_lds = self.parameters[-1].log_domain_size
        paths = jnp.asarray(
            np.stack(
                [aes.u128_to_limbs(p) for p in evaluation_points]
            ).astype(np.uint32)
        )
        # Host-precomputed static plan + per-call index data.
        plan = []
        start = 0
        block_indices = np.zeros((num_hl, n), dtype=np.int32)
        for hl, p in enumerate(self.parameters):
            stop = self._hierarchy_to_tree[hl]
            tree_rightshift = (
                evaluation_points_rightshift + last_lds - stop
            )
            bits = tuple(
                (stop - start) - 1 - j + tree_rightshift
                for j in range(stop - start)
            )
            plan.append((start, stop, bits))
            drs = (
                evaluation_points_rightshift
                + last_lds
                - p.log_domain_size
            )
            for k, pt in enumerate(evaluation_points):
                shifted = pt >> drs if drs < 128 else 0
                block_indices[hl, k] = self._domain_to_block_index(
                    shifted, hl
                )
            start = stop
        key = (n, tuple(plan))
        fn = self._fused_accumulate_cache.get(key)
        if fn is None:
            fn = _build_fused_accumulate(
                tuple(plan), vt, tuple(self._blocks_needed)
            )
            self._fused_accumulate_cache[key] = fn
        return fn(
            staged.seeds,
            staged.parties,
            paths,
            staged.cw_seeds,
            staged.cw_left,
            staged.cw_right,
            staged.value_corrections,
            jnp.asarray(level_masks),
            jnp.asarray(block_indices),
        )

    def evaluate_and_apply(self, keys: Sequence[DpfKey],
                           evaluation_points: Sequence[int],
                           op, evaluation_points_rightshift: int = 0,
                           staged: Optional[StagedKeyBatch] = None):
        """Evaluate many keys, each at its own point, across all hierarchy
        levels, calling `op(values_pytree, hierarchy_level)` after each level.

        `values_pytree` has leading dim len(keys). The engine behind DCF
        batch evaluation, mirroring `EvaluateAndApply`
        (`distributed_point_function.h:1072-1198`). Pass a `staged` batch
        from `stage_key_batch` to amortize key staging across calls (then
        `keys` may be None); everything per-level runs on device slices of
        the staged arrays.
        """
        if staged is None:
            if keys is None:
                raise ValueError("either keys or staged must be provided")
            staged = self.stage_key_batch(keys)
        elif keys is not None and len(keys) != staged.n:
            raise ValueError("keys and staged batch size mismatch")
        if staged.n != len(evaluation_points):
            raise ValueError("keys and evaluation_points size mismatch")
        last_lds = self.parameters[-1].log_domain_size
        seeds = staged.seeds
        control = staged.parties
        paths = jnp.asarray(
            np.stack(
                [aes.u128_to_limbs(p) for p in evaluation_points]
            ).astype(np.uint32)
        )

        start_level = 0
        for hl in range(len(self.parameters)):
            stop_level = self._hierarchy_to_tree[hl]
            tree_rightshift = (
                evaluation_points_rightshift
                + last_lds
                - stop_level
            )
            num_levels = stop_level - start_level
            if num_levels > 0:
                bit_indices = np.array(
                    [
                        num_levels - 1 - j + tree_rightshift
                        for j in range(num_levels)
                    ],
                    dtype=np.int32,
                )
                seeds, control = _eval_paths(
                    seeds,
                    control,
                    paths,
                    staged.cw_seeds[start_level:stop_level],
                    staged.cw_left[start_level:stop_level],
                    staged.cw_right[start_level:stop_level],
                    jnp.asarray(bit_indices),
                )
            start_level = stop_level

            # Leaf values at this hierarchy level, per key.
            vt = self.parameters[hl].value_type
            domain_rightshift = (
                evaluation_points_rightshift
                + last_lds
                - self.parameters[hl].log_domain_size
            )
            block_indices = []
            for pt in evaluation_points:
                shifted = pt >> domain_rightshift if domain_rightshift < 128 else 0
                block_indices.append(self._domain_to_block_index(shifted, hl))
            values = _leaf_stage_at(
                seeds,
                control,
                staged.value_corrections[hl],
                jnp.asarray(np.array(block_indices, dtype=np.int32)),
                vt,
                self._blocks_needed[hl],
                -1,  # party negation handled below per key
            )
            values = vt.dev_where(
                staged.parties != 0, vt.dev_neg(values), values
            )
            if op(values, hl) is False:
                break

    # -- internals ----------------------------------------------------------

    def _compute_value_correction(
        self, hierarchy_level: int, seeds: Sequence[int], alpha: int,
        beta, invert: bool,
    ) -> list:
        """Value correction at an output level (`ComputeValueCorrection`,
        `distributed_point_function.cc:81-117`)."""
        p = self.parameters[hierarchy_level]
        num_blocks = self._blocks_needed[hierarchy_level]
        bytes_a = _value_hash_bytes_host(seeds[0], num_blocks)
        bytes_b = _value_hash_bytes_host(seeds[1], num_blocks)
        shift = self.parameters[-1].log_domain_size - p.log_domain_size
        alpha_prefix = alpha >> shift if shift < 128 else 0
        index_in_block = self._domain_to_block_index(
            alpha_prefix, hierarchy_level
        )
        vt = p.value_type
        epb = vt.elements_per_block()
        if vt.can_convert_directly():
            ebytes = (vt.total_bit_size() + 7) // 8
            ints_a = [vt.parse_direct(bytes_a, i * ebytes) for i in range(epb)]
            ints_b = [vt.parse_direct(bytes_b, i * ebytes) for i in range(epb)]
        else:
            ints_a = [vt.from_bytes(bytes_a)]
            ints_b = [vt.from_bytes(bytes_b)]
        ints_b[index_in_block] = vt.add(ints_b[index_in_block], beta)
        out = []
        for a, b in zip(ints_a, ints_b):
            c = vt.sub(b, a)
            if invert:
                c = vt.neg(c)
            out.append(c)
        return out

    def _domain_to_tree_index(self, domain_index: int, hierarchy_level: int) -> int:
        bits = (
            self.parameters[hierarchy_level].log_domain_size
            - self._hierarchy_to_tree[hierarchy_level]
        )
        return domain_index >> bits

    def _domain_to_block_index(self, domain_index: int, hierarchy_level: int) -> int:
        bits = (
            self.parameters[hierarchy_level].log_domain_size
            - self._hierarchy_to_tree[hierarchy_level]
        )
        return domain_index & ((1 << bits) - 1)
