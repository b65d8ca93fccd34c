"""Sharded dense-PIR execution over a device mesh.

The reference scales within one server by Highway SIMD and threads
(SURVEY.md §2.9); the TPU-native scale-out axis is a `jax.sharding.Mesh`
with XLA collectives over ICI:

* **Query parallelism ("dp")** — each device expands the DPF trees of its
  slice of the query batch (AES work is embarrassingly parallel across
  keys), then `all_gather`s the packed selection blocks so every device
  sees the whole batch.
* **Database sharding ("tp" analog)** — the record axis of the database is
  sharded across the same devices; each device XORs its shard against all
  queries' selection bits.
* **Combine** — the per-device partials leave the `shard_map` still
  sharded over the mesh axis; the XOR reduction over that axis is written
  as a plain `jnp` reduce in the enclosing jit, and XLA lowers it to the
  collective (XOR has no `psum` twin, so rather than hand-rolling a
  gather+reduce inside the manual region — which the varying-manual-axes
  checker cannot prove replicated — the partition pass places it).

The public entry points build `shard_map`-wrapped jitted steps:
queries in → combined inner products out, everything device-resident, with
the sharding checker (`check_vma`) at its default (on).
"""

from __future__ import annotations

import os
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

from ..ops.inner_product import unpack_selection_bits
from ..pir.dense_eval import expansion_impl

U32 = jnp.uint32


def make_mesh(n_devices: int | None = None, axis_name: str = "x") -> Mesh:
    """1-D mesh over the first `n_devices` devices (default: all)."""
    devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if len(devices) < n_devices:
        raise ValueError(
            f"need {n_devices} devices, have {len(devices)}"
        )
    return Mesh(np.array(devices[:n_devices]), (axis_name,))


def _local_partial_ip(db_shard, selections, idx):
    """This device's XOR partial: its record rows against all queries.

    Slices the *packed* selection blocks to the local range before
    unpacking, so each device only materializes bits for its own records
    (r_local is a multiple of 128, so the range is whole blocks).
    """
    r_local = db_shard.shape[0]
    blocks_local = r_local // 128
    packed_local = lax.dynamic_slice_in_dim(
        selections, idx * blocks_local, blocks_local, axis=1
    )
    bits_local = unpack_selection_bits(packed_local)  # [nq, r_local]
    mask = (U32(0) - bits_local)[:, :, None]
    masked = mask & db_shard[None, :, :]
    return lax.reduce(
        masked, U32(0), lambda a, b: lax.bitwise_xor(a, b), (1,)
    )


def _xor_combine(partials: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """XOR-reduce `partials` (sharded over its leading axis) to a
    replicated result.

    The sharding constraint makes XLA all-gather the partials (supported
    on every backend) before a *local* XOR reduce — a plain reduce over a
    sharded axis would be partitioned into an XOR all-reduce, which e.g.
    the CPU backend rejects as an unsupported reduction computation.
    """
    partials = jax.lax.with_sharding_constraint(
        partials, NamedSharding(mesh, P())
    )
    return lax.reduce(
        partials, U32(0), lambda a, b: lax.bitwise_xor(a, b), (0,)
    )


def _check_divisible(name: str, value: int, divisor: int) -> None:
    if value % divisor != 0:
        raise ValueError(
            f"{name} (= {value}) must be divisible by {divisor}"
        )


def sum_shares_over_keys(
    values: jnp.ndarray, mesh: Mesh, axis_name: str = "x"
) -> jnp.ndarray:
    """Additive key-axis reduction for heavy-hitters share histograms:
    uint32[num_keys, P] sharded over keys -> replicated uint32[P].

    Unlike the PIR combine, the group law here is plain mod-2^32
    addition, so the reduction IS a `psum`: each device sums its key
    slice locally and the collective adds the per-device partials.
    `num_keys` must be divisible by the mesh size (callers fall back to
    a single-device `jnp.sum` otherwise).
    """
    ndev = mesh.devices.size
    _check_divisible("num_keys", values.shape[0], ndev)

    def step(local):
        return lax.psum(
            jnp.sum(local, axis=0, dtype=jnp.uint32), axis_name
        )

    fn = shard_map(
        step,
        mesh=mesh,
        in_specs=P(axis_name, None),
        out_specs=P(),
    )
    return jax.jit(fn)(values)


def sharded_inner_product(mesh: Mesh, axis_name: str = "x"):
    """Jitted XOR inner product with the database sharded over records.

    Returns fn(db_words uint32[R, W] sharded on axis 0,
               selections uint32[nq, B, 4] replicated) -> uint32[nq, W].
    `R` must be divisible by 128 * mesh size.
    """
    ndev = mesh.devices.size

    def step(db_shard, selections):
        idx = lax.axis_index(axis_name)
        partial = _local_partial_ip(db_shard, selections, idx)
        return partial[None]  # [1, nq, W], sharded over the mesh axis

    shard_mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis_name, None), P()),
        out_specs=P(axis_name),
    )

    @jax.jit
    def run(db_words, selections):
        _check_divisible("num_records", db_words.shape[0], 128 * ndev)
        partials = shard_mapped(db_words, selections)  # [ndev, nq, W]
        return _xor_combine(partials, mesh)

    return run


def sharded_dense_pir_step(
    mesh: Mesh,
    *,
    walk_levels: int,
    expand_levels: int,
    num_blocks: int,
    axis_name: str = "x",
    real_num_blocks: int | None = None,
):
    """Full dense-PIR step sharded over a mesh.

    Returns fn(seeds0[nq,4], control0[nq], cw_seeds[L,nq,4], cw_left[L,nq],
    cw_right[L,nq], last_vc[nq,4], db_words[R,W]) -> uint32[nq, W] where
    `nq` is divisible by the mesh size (query-parallel expansion) and `R`
    is divisible by 128*mesh size (record-sharded inner product).
    """
    multi = sharded_dense_pir_step_multi(
        mesh,
        walk_levels=walk_levels,
        expand_levels=expand_levels,
        num_blocks=num_blocks,
        num_databases=1,
        axis_name=axis_name,
        real_num_blocks=real_num_blocks,
    )

    def run(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            db_words):
        return multi(
            seeds0, control0, cw_seeds, cw_left, cw_right, last_vc, db_words
        )[0]

    return run


def sharded_dense_pir_step_multi(
    mesh: Mesh,
    *,
    walk_levels: int,
    expand_levels: int,
    num_blocks: int,
    num_databases: int,
    axis_name: str = "x",
    real_num_blocks: int | None = None,
):
    """Like `sharded_dense_pir_step`, but one expansion feeds XOR inner
    products against `num_databases` parallel databases sharing the record
    axis — the cuckoo-hashed sparse layout, where each bucket has a key
    row and a value row in two parallel dense databases
    (`cuckoo_hashed_dpf_pir_database.cc:164-183`): the DPF trees are
    expanded once per query, not once per sub-database.

    Returns fn(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
    *db_words) -> tuple of uint32[nq, W_i], with the same divisibility
    contract as `sharded_dense_pir_step`.

    `num_blocks` beyond the tree's 2^expand_levels leaf capacity is served
    by zero selection blocks (evaluate_selection_blocks pads) — correct
    only when every block past the capacity holds mesh-padding zero rows.
    Callers that know the real (pre-padding) block count pass it as
    `real_num_blocks` so a genuinely undersized tree errors instead of
    answering real records with zero shares.
    """
    ndev = mesh.devices.size
    if (
        real_num_blocks is not None
        and real_num_blocks > (1 << expand_levels)
    ):
        raise ValueError(
            f"DPF tree leaf capacity 2^{expand_levels} cannot cover the "
            f"{real_num_blocks} real record blocks; only mesh-padding "
            "blocks may lie beyond the tree"
        )
    # The expansion below runs inside a shard_map trace, where the level
    # kernels' on-device self-check cannot run; run it here, eagerly, so
    # a kernel that miscompiles raises before the traced program builds.
    from ..pir.dense_eval_planes import warm_level_kernels

    warm_level_kernels()

    def step(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
             *db_shards):
        sel_local = expansion_impl()(
            seeds0,
            control0,
            cw_seeds,
            cw_left,
            cw_right,
            last_vc,
            walk_levels=walk_levels,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
        )
        sel_all = lax.all_gather(sel_local, axis_name, tiled=True)
        idx = lax.axis_index(axis_name)
        return tuple(
            _local_partial_ip(db_shard, sel_all, idx)[None]
            for db_shard in db_shards
        )

    shard_mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(
            P(axis_name),
            P(axis_name),
            P(None, axis_name),
            P(None, axis_name),
            P(None, axis_name),
            P(axis_name),
        ) + (P(axis_name, None),) * num_databases,
        out_specs=(P(axis_name),) * num_databases,
    )

    @jax.jit
    def run(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            *db_words):
        if len(db_words) != num_databases:
            raise ValueError(
                f"expected {num_databases} databases, got {len(db_words)}"
            )
        _check_divisible("num_queries", seeds0.shape[0], ndev)
        for db in db_words:
            _check_divisible("num_records", db.shape[0], 128 * ndev)
            if db.shape[0] != num_blocks * 128:
                raise ValueError(
                    f"database has {db.shape[0]} rows but the step was "
                    f"built for num_blocks={num_blocks} "
                    f"({num_blocks * 128} rows)"
                )
        partials = shard_mapped(
            seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            *db_words,
        )
        return tuple(_xor_combine(p, mesh) for p in partials)

    return run


def shard_database(mesh: Mesh, db_words: jnp.ndarray, axis_name: str = "x"):
    """Place a database buffer sharded over its record axis."""
    from ..observability.device import default_telemetry

    return default_telemetry().transfers.device_put(
        db_words, NamedSharding(mesh, P(axis_name, None)),
        phase="db_staging",
    )


def pad_rows_to_mesh(db_words: jnp.ndarray, ndev: int) -> jnp.ndarray:
    """Zero-pad record rows to a multiple of 128 * ndev (zero rows never
    contribute to a XOR inner product)."""
    pad = (-db_words.shape[0]) % (128 * ndev)
    if pad:
        db_words = jnp.concatenate(
            [db_words, jnp.zeros((pad, db_words.shape[1]), db_words.dtype)]
        )
    return db_words


def pad_staged_queries(staged, ndev: int):
    """Zero-pad a `stage_keys` tuple's query axis to a multiple of ndev.

    Layout: seeds0[nq,4], control0[nq], cw_seeds[L,nq,4], cw_left[L,nq],
    cw_right[L,nq], last_vc[nq,4]. Zero keys are inert (their expansion
    selects nothing real and the caller drops the padded outputs).
    """
    nq = staged[0].shape[0]
    pad = (-nq) % ndev
    if not pad:
        return staged
    s0, c0, cs, cl, cr, vc = (np.asarray(a) for a in staged)
    return (
        np.pad(s0, ((0, pad), (0, 0))),
        np.pad(c0, ((0, pad),)),
        np.pad(cs, ((0, 0), (0, pad), (0, 0))),
        np.pad(cl, ((0, 0), (0, pad))),
        np.pad(cr, ((0, 0), (0, pad))),
        np.pad(vc, ((0, pad), (0, 0))),
    )


def stage_sharded_bitmajor(mesh: Mesh, db_words, axis_name: str = "x"):
    """Stage a record-sharded database into bit-major MXU layout per
    shard: uint32[R, W] sharded on records -> uint32[32, G, W] sharded on
    the group axis (records never leave their device; the permutation is
    `permute_db_bitmajor` applied shard-locally).

    R must be divisible by 4096 * mesh size so every shard permutes
    without padding (4096 = 32 bit-classes x 128 lane groups).
    """
    from ..ops.inner_product_pallas import permute_db_bitmajor

    ndev = mesh.devices.size
    _check_divisible("num_records", db_words.shape[0], 4096 * ndev)
    fn = jax.jit(
        shard_map(
            permute_db_bitmajor,
            mesh=mesh,
            in_specs=P(axis_name, None),
            out_specs=P(None, axis_name, None),
        )
    )
    return fn(db_words)


def _local_partial_ip_mxu(db_perm_shard, selections, idx, interpret,
                          axis_name):
    """This device's XOR partial via the v2 Pallas MXU kernel on its
    bit-major shard (`ops/inner_product_pallas.py`): the multi-chip
    analog of the single-chip pallas2 serving tier."""
    from ..ops.inner_product_pallas import xor_inner_product_pallas2_staged

    g_local = db_perm_shard.shape[1]
    nq = selections.shape[0]
    packed = selections.reshape(nq, -1)
    packed_local = lax.dynamic_slice_in_dim(
        packed, idx * g_local, g_local, axis=1
    )
    return xor_inner_product_pallas2_staged(
        db_perm_shard,
        packed_local.reshape(nq, -1, 4),
        interpret=interpret,
        vma=(axis_name,),
    )


def sharded_dense_pir_step_mxu(
    mesh: Mesh,
    *,
    walk_levels: int,
    expand_levels: int,
    num_blocks: int,
    num_databases: int = 1,
    axis_name: str = "x",
    real_num_blocks: int | None = None,
    interpret: bool = False,
):
    """`sharded_dense_pir_step_multi` with the local inner product on the
    MXU: databases arrive bit-major staged (`stage_sharded_bitmajor`,
    uint32[32, G, W] sharded on the group axis) and each device runs the
    v2 Pallas kernel on its shard. `interpret=True` runs the kernel in
    interpret mode (CPU-mesh tests of the multi-chip wiring).

    Returns fn(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
    *db_perms) -> tuple of uint32[nq, W_i].
    """
    ndev = mesh.devices.size
    if (
        real_num_blocks is not None
        and real_num_blocks > (1 << expand_levels)
    ):
        raise ValueError(
            f"DPF tree leaf capacity 2^{expand_levels} cannot cover the "
            f"{real_num_blocks} real record blocks; only mesh-padding "
            "blocks may lie beyond the tree"
        )
    # The expansion below runs inside a shard_map trace, where the level
    # kernels' on-device self-check cannot run; run it here, eagerly, so
    # a kernel that miscompiles raises before the traced program builds.
    from ..pir.dense_eval_planes import warm_level_kernels

    warm_level_kernels()

    def step(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
             *db_shards):
        sel_local = expansion_impl()(
            seeds0,
            control0,
            cw_seeds,
            cw_left,
            cw_right,
            last_vc,
            walk_levels=walk_levels,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
        )
        sel_all = lax.all_gather(sel_local, axis_name, tiled=True)
        idx = lax.axis_index(axis_name)
        return tuple(
            _local_partial_ip_mxu(
                db_shard, sel_all, idx, interpret, axis_name
            )[None]
            for db_shard in db_shards
        )

    shard_mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(
            P(axis_name),
            P(axis_name),
            P(None, axis_name),
            P(None, axis_name),
            P(None, axis_name),
            P(axis_name),
        ) + (P(None, axis_name, None),) * num_databases,
        out_specs=(P(axis_name),) * num_databases,
    )

    @jax.jit
    def run(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            *db_perms):
        if len(db_perms) != num_databases:
            raise ValueError(
                f"expected {num_databases} databases, got {len(db_perms)}"
            )
        _check_divisible("num_queries", seeds0.shape[0], ndev)
        for db in db_perms:
            if db.ndim != 3 or db.shape[0] != 32:
                raise ValueError(
                    "databases must be bit-major staged "
                    "(stage_sharded_bitmajor)"
                )
            _check_divisible("num_groups", db.shape[1], 128 * ndev)
            if db.shape[1] * 32 != num_blocks * 128:
                raise ValueError(
                    f"database has {db.shape[1] * 32} rows but the step "
                    f"was built for num_blocks={num_blocks}"
                )
        partials = shard_mapped(
            seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            *db_perms,
        )
        return tuple(_xor_combine(p, mesh) for p in partials)

    return run


def stage_streaming_chunks(mesh: Mesh, db_chunks, axis_name: str = "x"):
    """Place a streaming chunk staging (`database.streaming_chunks`
    layout: uint32[nc, ...] row- or bit-major per chunk) sharded over the
    chunk axis: each device holds a contiguous span of scan steps."""
    from ..observability.device import default_telemetry

    spec = P(*((axis_name,) + (None,) * (db_chunks.ndim - 1)))
    return default_telemetry().transfers.device_put(
        db_chunks, NamedSharding(mesh, spec), phase="db_staging"
    )


def sharded_dense_pir_step_streaming(
    mesh: Mesh,
    *,
    walk_levels: int,
    cut_levels: int,
    chunk_levels: int,
    axis_name: str = "x",
    ip: str = "jnp",
    interpret: bool = False,
):
    """Streaming variant of `sharded_dense_pir_step_mxu`: the fused
    expand->inner-product scan with the *chunk* axis sharded over the
    mesh instead of the record axis.

    Keys arrive replicated; every device expands the cheap covering
    subtree down to the cut, slices out its own span of
    `2^cut_levels / ndev` cut-state lanes by `axis_index`, and scans its
    local database chunks, accumulating per-shard XOR partials that are
    combined once at the end. No selection tensor is ever materialized
    or all-gathered — per-device peak selection bytes drop by the mesh
    factor on top of the streaming chunk bound.

    Returns fn(seeds0[nq,4], control0[nq], cw_seeds[L,nq,4],
    cw_left[L,nq], cw_right[L,nq], last_vc[nq,4],
    db_chunks uint32[2^cut_levels, ...] sharded on axis 0
    (`stage_streaming_chunks`; bit-major per chunk for ip="pallas2"))
    -> uint32[nq, W]. `2^cut_levels` must be divisible by the mesh size.
    """
    from ..pir.dense_eval_planes_v2 import (
        _packed_levels,
        _pad_keys32,
        pack_key_planes_kg,
        streaming_cut_state,
        streaming_scan_accumulate,
    )

    ndev = mesh.devices.size
    num_chunks = 1 << cut_levels
    _check_divisible("num_chunks", num_chunks, ndev)
    nc_local = num_chunks // ndev
    levels = walk_levels + cut_levels + chunk_levels

    def step(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
             db_chunks_shard):
        nk = seeds0.shape[0]
        seeds0, control0, cw_seeds, cw_left, cw_right, last_vc = (
            _pad_keys32(
                seeds0, control0, cw_seeds, cw_left, cw_right, last_vc
            )
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
        idx = lax.axis_index(axis_name)
        state = lax.dynamic_slice_in_dim(
            state, idx * nc_local, nc_local, axis=-1
        )
        ctrl = lax.dynamic_slice_in_dim(
            ctrl, idx * nc_local, nc_local, axis=-1
        )
        tail_cwp, tail_cwl, tail_cwr = _packed_levels(
            cw_seeds, cw_left, cw_right, walk_levels + cut_levels, levels
        )
        acc = streaming_scan_accumulate(
            state,
            ctrl,
            db_chunks_shard,
            tail_cwp,
            tail_cwl,
            tail_cwr,
            pack_key_planes_kg(last_vc),
            ip=ip,
            interpret=interpret,
            vma=(axis_name,),
        )
        return acc[None, :nk]

    shard_mapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), P(axis_name)),
        out_specs=P(axis_name),
    )

    @jax.jit
    def run(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            db_chunks):
        if cw_seeds.shape[0] != levels:
            raise ValueError(
                f"key has {cw_seeds.shape[0]} correction levels; step "
                f"was built for walk {walk_levels} + cut {cut_levels} + "
                f"chunk {chunk_levels}"
            )
        if db_chunks.shape[0] != num_chunks:
            raise ValueError(
                f"expected {num_chunks} database chunks, got "
                f"{db_chunks.shape[0]}"
            )
        partials = shard_mapped(
            seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
            db_chunks,
        )
        return _xor_combine(partials, mesh)

    return run


def make_mesh2d(
    shard_devices: int | None = None,
    key_devices: int | None = None,
    *,
    axis_names: tuple[str, str] = ("shard", "key"),
) -> Mesh:
    """2-D serving mesh: database-shard axis x key-batch axis.

    Row-major over `jax.devices()`, so the key axis is the fast
    (ICI-near) one. Leaving one count `None` derives it from the device
    count; leaving both defaults to all devices on the shard axis.
    """
    devices = jax.devices()
    n = len(devices)
    if shard_devices is None and key_devices is None:
        shard_devices, key_devices = n, 1
    elif shard_devices is None:
        _check_divisible("device count", n, key_devices)
        shard_devices = n // key_devices
    elif key_devices is None:
        _check_divisible("device count", n, shard_devices)
        key_devices = n // shard_devices
    if shard_devices < 1 or key_devices < 1:
        raise ValueError("mesh axes must be >= 1 device each")
    need = shard_devices * key_devices
    if need > n:
        raise ValueError(
            f"mesh {shard_devices}x{key_devices} needs {need} devices, "
            f"have {n}"
        )
    grid = np.array(devices[:need]).reshape(shard_devices, key_devices)
    return Mesh(grid, tuple(axis_names))


class ScratchPool:
    """Device-resident selection-scratch buffers recycled across requests.

    The serving entry point takes a zeroed scratch accumulator as its
    donated argument and returns a re-zeroed buffer alongside the result;
    the pool hands the returned buffer to the next same-shape request, so
    steady-state serving stages the scratch exactly once per shape
    instead of once per request. `DPF_TPU_DONATE=0` (or enabled=False)
    disables recycling — every request stages a fresh copy — which is the
    control arm the donation test and bench history measure against.
    """

    def __init__(self, mesh: Mesh, enabled: bool = True):
        self._mesh = mesh
        self._enabled = bool(enabled)
        self._cache: dict[tuple, jnp.ndarray] = {}
        self._lock = threading.Lock()
        self.staged_copies = 0
        self.reuses = 0

    def take(self, shape) -> jnp.ndarray:
        key = tuple(int(s) for s in shape)
        if self._enabled:
            with self._lock:
                buf = self._cache.pop(key, None)
            if buf is not None:
                self.reuses += 1
                return buf
        from ..observability.device import default_telemetry

        buf = default_telemetry().transfers.device_put(
            np.zeros(key, np.uint32),
            NamedSharding(self._mesh, P()),
            phase="selection_scratch",
        )
        self.staged_copies += 1
        return buf

    def put(self, buf) -> None:
        if not self._enabled:
            return
        key = tuple(int(s) for s in buf.shape)
        with self._lock:
            self._cache[key] = buf

    def export(self) -> dict:
        with self._lock:
            shapes = sorted(self._cache)
        return {
            "enabled": self._enabled,
            "staged_copies": int(self.staged_copies),
            "reuses": int(self.reuses),
            "cached_shapes": [list(s) for s in shapes],
        }


class ShardedServingPlan:
    """One jitted serving step over a 2-D mesh (shard axis x key axis).

    The streaming expand->inner-product scan runs per device on its
    (chunk-span x key-slice) tile: keys arrive pre-partitioned over the
    key axis (`stage_keys`), database chunks pre-partitioned over the
    shard axis (`DenseDpfPirDatabase.streaming_chunks(mesh=...)`), so
    dispatch never relayouts on the host. Per-device partials XOR-combine
    to a replicated result inside the jit.

    The selection scratch is donated (`donate_argnums`): the entry takes
    a zeroed accumulator, folds it into the combine, and returns a
    re-zeroed buffer that the `ScratchPool` recycles into the next
    request — ROADMAP 3a's donation win, measurable as
    `selection_scratch` copies in the TransferLedger.
    """

    def __init__(
        self,
        mesh: Mesh,
        *,
        walk_levels: int,
        cut_levels: int,
        chunk_levels: int,
        ip: str = "jnp",
        interpret: bool = False,
        donate: bool | None = None,
    ):
        axis_names = tuple(mesh.axis_names)
        if len(axis_names) != 2:
            raise ValueError(
                f"ShardedServingPlan needs a 2-D mesh, got axes "
                f"{axis_names}"
            )
        self.mesh = mesh
        self.shard_axis, self.key_axis = axis_names
        self.num_shards = int(mesh.shape[self.shard_axis])
        self.num_key_devices = int(mesh.shape[self.key_axis])
        self.walk_levels = int(walk_levels)
        self.cut_levels = int(cut_levels)
        self.chunk_levels = int(chunk_levels)
        self.num_chunks = 1 << self.cut_levels
        _check_divisible("num_chunks", self.num_chunks, self.num_shards)
        self.ip = ip
        self.bitmajor = ip == "pallas2"
        if donate is None:
            donate = os.environ.get("DPF_TPU_DONATE", "1") != "0"
        self.donate = bool(donate)
        self.scratch = ScratchPool(mesh, enabled=self.donate)
        self.requests = 0
        self._levels = self.walk_levels + self.cut_levels + self.chunk_levels
        # CPU/XLA may decline the replicated-scratch alias; the pool's
        # recycling still holds, so the warning is noise.
        warnings.filterwarnings(
            "ignore", message=".*donated buffers were not usable.*"
        )
        self._entry = self._build(interpret)

    def _build(self, interpret: bool):
        from ..pir.dense_eval_planes_v2 import (
            _packed_levels,
            _pad_keys32,
            pack_key_planes_kg,
            streaming_cut_state,
            streaming_scan_accumulate,
        )

        mesh = self.mesh
        sa, ka = self.shard_axis, self.key_axis
        nc_local = self.num_chunks // self.num_shards
        walk_levels, cut_levels = self.walk_levels, self.cut_levels
        levels, ip = self._levels, self.ip

        def step(seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
                 db_chunks_shard):
            nk = seeds0.shape[0]  # local key-slice size
            seeds0, control0, cw_seeds, cw_left, cw_right, last_vc = (
                _pad_keys32(
                    seeds0, control0, cw_seeds, cw_left, cw_right, last_vc
                )
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
            idx = lax.axis_index(sa)
            state = lax.dynamic_slice_in_dim(
                state, idx * nc_local, nc_local, axis=-1
            )
            ctrl = lax.dynamic_slice_in_dim(
                ctrl, idx * nc_local, nc_local, axis=-1
            )
            tail_cwp, tail_cwl, tail_cwr = _packed_levels(
                cw_seeds, cw_left, cw_right, walk_levels + cut_levels,
                levels,
            )
            acc = streaming_scan_accumulate(
                state,
                ctrl,
                db_chunks_shard,
                tail_cwp,
                tail_cwl,
                tail_cwr,
                pack_key_planes_kg(last_vc),
                ip=ip,
                interpret=interpret,
                vma=(sa, ka),
            )
            return acc[None, :nk]

        shard_mapped = shard_map(
            step,
            mesh=mesh,
            in_specs=(
                P(ka),
                P(ka),
                P(None, ka),
                P(None, ka),
                P(None, ka),
                P(ka),
                P(sa),
            ),
            out_specs=P(sa, ka),
        )

        def entry(scratch, seeds0, control0, cw_seeds, cw_left, cw_right,
                  last_vc, db_chunks):
            partials = shard_mapped(
                seeds0, control0, cw_seeds, cw_left, cw_right, last_vc,
                db_chunks,
            )
            combined = _xor_combine(partials, mesh) ^ scratch
            return combined, jnp.zeros_like(scratch)

        return jax.jit(entry, donate_argnums=(0,))

    def stage_keys(self, staged_host):
        """Place a host-side key staging tuple onto the mesh,
        pre-partitioned over the key axis.

        Zero-pads the query axis to a multiple of the key-axis size
        (zero keys are inert; callers slice the result back). The six
        blocks go up in one batched `device_put`, counted as a single
        `key_staging` h2d copy — the same one-copy-per-batch convention
        as the single-device `stage_keys`.
        """
        staged = pad_staged_queries(staged_host, self.num_key_devices)
        ka = self.key_axis
        specs = (P(ka), P(ka), P(None, ka), P(None, ka), P(None, ka),
                 P(ka))
        arrays = tuple(
            np.ascontiguousarray(np.asarray(a)) for a in staged
        )
        shardings = tuple(NamedSharding(self.mesh, s) for s in specs)
        dev = jax.device_put(arrays, shardings)
        from ..observability.device import default_telemetry

        default_telemetry().transfers.record_h2d(
            sum(int(a.nbytes) for a in arrays),
            phase="key_staging",
            copies=1,
        )
        return dev

    def run(self, staged_dev, db_chunks):
        """Execute on pre-placed inputs.

        Returns replicated uint32[nq_padded, W]; callers slice back to
        the real key count.
        """
        if staged_dev[2].shape[0] != self._levels:
            raise ValueError(
                f"key has {staged_dev[2].shape[0]} correction levels; "
                f"plan was built for walk {self.walk_levels} + cut "
                f"{self.cut_levels} + chunk {self.chunk_levels}"
            )
        if db_chunks.shape[0] != self.num_chunks:
            raise ValueError(
                f"expected {self.num_chunks} database chunks, got "
                f"{db_chunks.shape[0]}"
            )
        nk = int(staged_dev[0].shape[0])
        w = int(db_chunks.shape[-1])
        scratch = self.scratch.take((nk, w))
        t_dispatch = time.monotonic()
        out, fresh = self._entry(scratch, *staged_dev, db_chunks)
        self.scratch.put(fresh)
        self.requests += 1
        # Per-shard utilization rows: SPMD runs every shard for the
        # same dispatch wall, so each participating shard is credited
        # the step's wall time; skew between shards then shows up in
        # the tracker's per-window busy ratios (straggler watch).
        try:
            from ..observability.utilization import (
                default_utilization_tracker,
            )

            wall_s = time.monotonic() - t_dispatch
            tracker = default_utilization_tracker()
            for shard in range(self.num_shards):
                tracker.record_shard_busy(shard, wall_s)
        except Exception:  # noqa: BLE001 - accounting never breaks serving
            pass
        return out

    def export(self) -> dict:
        return {
            "axes": {
                "shard": {"name": self.shard_axis,
                          "size": self.num_shards},
                "key": {"name": self.key_axis,
                        "size": self.num_key_devices},
            },
            "devices": int(self.mesh.devices.size),
            "walk_levels": self.walk_levels,
            "cut_levels": self.cut_levels,
            "chunk_levels": self.chunk_levels,
            "num_chunks": self.num_chunks,
            "ip": self.ip,
            "bitmajor": self.bitmajor,
            "donate": self.donate,
            "requests": int(self.requests),
            "scratch": self.scratch.export(),
        }
