"""Dense in-memory PIR database living in device HBM.

The reference packs all records into one 128-bit-aligned host buffer and
XORs with Highway SIMD (`pir/dense_dpf_pir_database.h:101-111`,
`.cc:112-161`). The TPU redesign packs records into a single
`uint32[num_records_padded, record_words]` array resident in HBM: every
record is zero-padded to the maximum record size, and the record count is
padded to a multiple of 128 so whole selection blocks line up with rows.

`inner_product_with` serves the whole query batch in one database pass
through ONE inner-product tier (`inner_product_tier`): on TPU the v2
Pallas MXU kernel (`ops/inner_product_pallas.py:
xor_inner_product_pallas2_staged`, one large int8 dot per tile,
bit-major layout staged once on first use); elsewhere (CPU tests) the
jitted jnp XOR-reduction. A failure raises: no tier stands in for
another. ``DPF_TPU_INNER_PRODUCT=pallas2|pallas|bitplane|jnp`` forces a
tier (the v1 Pallas kernel and the pure-jnp MXU bit-plane path are
f32-exact only to 2^24 records).
"""

from __future__ import annotations

import functools
import os
import threading
import time
import warnings
import weakref
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import tracing
from ..observability.device import default_telemetry

from ..ops.inner_product import (
    xor_inner_product,
    xor_inner_product_bitplane,
)
from ..ops.inner_product_pallas import (
    permute_db_bitmajor,
    xor_inner_product_pallas2_staged,
    xor_inner_product_pallas_staged,
)


def _v2_tile_knobs() -> dict:
    """Serving-time tile overrides for the v2 MXU kernel
    (DPF_TPU_IP_TQ / DPF_TPU_IP_TG / DPF_TPU_IP_JC), so a capture window
    can A/B the serving path's own tiles without code edits. Unset,
    malformed, or invalid values keep the kernel defaults — a bad knob
    must not knock the pallas2 tier out of serving for the process."""
    knobs = {}
    for env, key, valid in (
        ("DPF_TPU_IP_TQ", "tile_queries", lambda v: v > 0),
        ("DPF_TPU_IP_TG", "tile_groups", lambda v: v > 0),
        ("DPF_TPU_IP_JC", "j_chunk", lambda v: v > 0 and 32 % v == 0),
    ):
        raw = os.environ.get(env, "")
        if not raw:
            continue
        try:
            val = int(raw)
        except ValueError:
            val = None
        if val is None or not valid(val):
            warnings.warn(
                f"{env}={raw!r} is not a valid {key}; keeping the "
                "kernel default"
            )
            continue
        knobs[key] = val
    return knobs


_PIPELINE_ENV = "DPF_TPU_PIPELINED_STAGING"


def inner_product_tier() -> str:
    """The inner-product tier this process serves: pallas2 on TPU, jnp
    elsewhere, or the tier `DPF_TPU_INNER_PRODUCT` forces."""
    mode = os.environ.get("DPF_TPU_INNER_PRODUCT", "auto")
    if mode == "auto":
        return "pallas2" if jax.default_backend() == "tpu" else "jnp"
    return mode


def tier_inner_product(tier: str):
    """(db, selections) -> uint32[nq, W] for `tier`; every tier but jnp
    reads the bit-major staging (`permute_db_bitmajor`)."""
    if tier == "pallas2":
        return functools.partial(
            xor_inner_product_pallas2_staged, **_v2_tile_knobs()
        )
    if tier == "pallas":
        return xor_inner_product_pallas_staged
    if tier == "bitplane":
        return xor_inner_product_bitplane
    if tier == "jnp":
        return xor_inner_product
    raise ValueError(f"unknown DPF_TPU_INNER_PRODUCT tier {tier!r}")


def pipelined_staging_enabled() -> bool:
    """Whether chunked device stagings upload piece-by-piece on JAX's
    async dispatch stream (one final sync) instead of one synchronous
    full-image `device_put`. On by default; set
    DPF_TPU_PIPELINED_STAGING=0 to restore the upfront path (the
    bench's A/B baseline). Read per staging call so tests and capture
    windows can flip it without rebuilding databases."""
    return os.environ.get(_PIPELINE_ENV, "1").strip().lower() not in (
        "0", "false", "off", "no",
    )


def _stage_pieces_pipelined(ledger, pieces, assemble,
                            phase: str = "db_staging"):
    """Pipelined H2D staging: each host piece is its own counted async
    `device_put` — JAX's dispatch queue bounds the copy stream (the
    host runs at most one piece ahead of the DMA engine, the
    double-buffer depth) with no per-piece sync — then `assemble`
    combines the parts device-side and ONE final counted sync drains
    everything. The wall time between the first put returning and that
    final sync is host work (issuing the remaining uploads,
    dispatching the assembly) performed while copies were already in
    flight; the ledger accumulates it as the phase's `overlapped_ms`,
    the hidden half of the phase's transfer time."""
    parts = []
    t_first = None
    for piece in pieces:
        parts.append(ledger.device_put(piece, phase=phase))
        if t_first is None:
            t_first = time.perf_counter()
    arr = assemble(parts)
    if t_first is not None:
        ledger.record_overlap((time.perf_counter() - t_first) * 1e3, phase)
    return ledger.block_until_ready(arr, phase=phase)


def words_to_record_bytes(
    out: np.ndarray, num_keys: int, size: int
) -> List[bytes]:
    """uint32[nq, W] inner products -> per-query record byte strings.

    Little-endian words, truncated to the database's record size (the
    reference's result convention, `inner_product_hwy.cc:271-272`). The
    single home of this codec — the servers' sharded/chunked paths and the
    database all share it.
    """
    raw = np.ascontiguousarray(out[:num_keys].astype("<u4")).view(np.uint8)
    return [raw[q, :size].tobytes() for q in range(num_keys)]


class DenseDpfPirDatabase:
    """Immutable dense database; construct via `DenseDpfPirDatabase.Builder`.

    Every database carries a **generation** tag (`generation`, default
    0): a monotonically increasing snapshot version the serving runtime
    (`serving/snapshots.py`) binds batches and wire envelopes to, so a
    rotated deployment can prove both parties answered one query from
    the same data. `Builder.build_from(prev)` derives generation N+1
    from N host-side — staged `update(i, value)` rows are repacked in
    place of a full re-insert when they fit the previous layout.
    """

    class Builder:
        def __init__(self):
            self._records: List[bytes] = []
            # index -> staged replacement, applied by build()/build_from()
            self._updates: dict = {}

        def insert(self, value: bytes) -> "DenseDpfPirDatabase.Builder":
            if isinstance(value, str):
                value = value.encode()
            self._records.append(bytes(value))
            return self

        def update(self, i: int, value: bytes) -> "DenseDpfPirDatabase.Builder":
            """Stage an in-place replacement of record `i`. Under
            `build()` the index refers to this builder's inserted
            records; under `build_from(prev)` it refers to `prev`'s
            records — the delta path that makes generation N+1 cheap."""
            if isinstance(value, str):
                value = value.encode()
            i = int(i)
            if i < 0:
                raise IndexError(f"update index {i} must be >= 0")
            self._updates[i] = bytes(value)
            return self

        def clone(self) -> "DenseDpfPirDatabase.Builder":
            b = DenseDpfPirDatabase.Builder()
            b._records = list(self._records)
            b._updates = dict(self._updates)
            return b

        def build(self) -> "DenseDpfPirDatabase":
            records = list(self._records)
            for i, value in self._updates.items():
                if i >= len(records):
                    raise IndexError(
                        f"update index {i} out of bounds for "
                        f"{len(records)} inserted records"
                    )
                records[i] = value
            return DenseDpfPirDatabase(records)

        def build_from(
            self, prev: "DenseDpfPirDatabase"
        ) -> "DenseDpfPirDatabase":
            """Derive generation N+1 from database `prev` host-side:
            `prev`'s records with this builder's staged `update`s
            applied (and any `insert`ed records appended), tagged
            `prev.generation + 1`. When nothing is appended and every
            updated value fits `prev`'s packed row layout, the packed
            host buffer is copied and only the updated rows repacked —
            no per-record re-insert at directory scale."""
            for i in self._updates:
                if i >= prev.size:
                    raise IndexError(
                        f"update index {i} out of bounds for previous "
                        f"generation of {prev.size} records"
                    )
            generation = prev.generation + 1
            fits_in_place = not self._records and all(
                len(v) <= prev._max_value_size
                for v in self._updates.values()
            )
            if fits_in_place:
                return DenseDpfPirDatabase._from_delta(
                    prev, self._updates, generation
                )
            records = list(prev._records)
            for i, value in self._updates.items():
                records[i] = value
            records.extend(self._records)
            return DenseDpfPirDatabase(records, generation=generation)

    def __init__(self, records: Sequence[bytes], generation: int = 0):
        self._records = [bytes(r) for r in records]
        self._generation = int(generation)
        self._max_value_size = max((len(r) for r in self._records), default=0)
        num_records = len(self._records)
        self._num_padded = max(128, ((num_records + 127) // 128) * 128)
        record_bytes = max(4, ((self._max_value_size + 3) // 4) * 4)
        buf = np.zeros((self._num_padded, record_bytes), dtype=np.uint8)
        # Vectorized variable-length packing (chunked): a per-record Python
        # loop is minutes of host time at the sparse-PIR benchmark scale
        # (1.5 * 2^24 buckets).
        chunk = 1 << 20
        for s in range(0, num_records, chunk):
            rs = self._records[s : s + chunk]
            data = np.frombuffer(b"".join(rs), dtype=np.uint8)
            if data.size == 0:
                continue
            lengths = np.fromiter(
                (len(r) for r in rs), dtype=np.int64, count=len(rs)
            )
            ends = np.cumsum(lengths)
            starts = ends - lengths
            rows = np.repeat(np.arange(s, s + len(rs)), lengths)
            cols = np.arange(data.size, dtype=np.int64) - np.repeat(
                starts, lengths
            )
            buf[rows, cols] = data
        # Host copy; device staging is lazy so the Pallas path only ever
        # holds the bit-major layout in HBM (not both layouts).
        self._host_words = np.ascontiguousarray(buf).view("<u4").astype(
            np.uint32
        )
        self._init_runtime()

    def _init_runtime(self) -> None:
        """Device-staging slots (fresh per instance — a delta build
        shares host bytes, never stagings)."""
        self._db_words = None  # row-major device copy (jnp tier)
        self._db_perm = None  # bit-major layout, staged on first pallas use
        # Bitrev-block staging (the v2 gather-free serving exit): same
        # records with 128-record blocks bit-reversal-permuted, padded
        # to a power-of-two block count. Built lazily; a process serving
        # one expansion mode holds one staging.
        self._host_rev = None
        self._db_words_rev = None
        self._db_perm_rev = None
        # Streaming staging (blocked-bitrev chunk spans), one plan at a
        # time: ((cut_levels, bitmajor[, mesh fingerprint]),
        # uint32[nc, ...] device array — mesh-sharded when staged with
        # `mesh=`).
        self._streaming_stage = None
        # Per-shard detail of the last mesh staging (statusz/bundles).
        self._mesh_staging_info = None
        # All lazy stagings build under this lock: concurrent first
        # requests must not stage the database twice (each staging is a
        # full HBM copy). Reentrant because _staged_perm -> _row_words
        # -> _host_words_bitrev nest.
        self._stage_lock = threading.RLock()
        # Delta-build lineage (`_from_delta` fills these): the previous
        # generation (weakly, so a rotation chain never retains every
        # ancestor's host image) and the sorted updated row indices.
        # When the base's staging is still resident, `prestage()` /
        # `db_words` / `streaming_chunks` scatter only these rows'
        # chunks into a NEW device buffer instead of re-uploading the
        # full image.
        self._delta_base = None
        self._delta_rows = None
        # {mode, bytes_staged, bytes_full_image, bytes_saved,
        #  generation} for the most recent prestage() call —
        # snapshots/statusz/bench read the delta ratio here.
        self.last_prestage_stats = None

    @classmethod
    def _from_delta(
        cls,
        prev: "DenseDpfPirDatabase",
        updates: dict,
        generation: int,
    ) -> "DenseDpfPirDatabase":
        """Generation N+1 from N without re-inserting: copy the packed
        host buffer and repack only the updated rows. Caller guarantees
        every update index is in range and every value fits `prev`'s
        record width (`build_from` checks and falls back otherwise)."""
        db = cls.__new__(cls)
        records = list(prev._records)
        for i, value in updates.items():
            records[i] = value
        db._records = records
        db._generation = int(generation)
        db._max_value_size = prev._max_value_size
        db._num_padded = prev._num_padded
        host = prev._host_words.copy()
        record_bytes = host.shape[1] * 4
        for i, value in updates.items():
            row = np.zeros(record_bytes, dtype=np.uint8)
            row[: len(value)] = np.frombuffer(value, dtype=np.uint8)
            host[i] = row.view("<u4").astype(np.uint32)
        db._host_words = host
        db._init_runtime()
        db._delta_base = weakref.ref(prev)
        db._delta_rows = sorted(int(i) for i in updates)
        return db

    @property
    def size(self) -> int:
        """Number of records."""
        return len(self._records)

    @property
    def generation(self) -> int:
        """Snapshot generation tag (0 = untagged / initial build)."""
        return self._generation

    @property
    def max_value_size(self) -> int:
        return self._max_value_size

    @property
    def num_selection_bits(self) -> int:
        """Selection bits a query must provide (padded record count)."""
        return self._num_padded

    @property
    def num_selection_blocks(self) -> int:
        return self._num_padded // 128

    @property
    def db_words(self) -> jnp.ndarray:
        """uint32[num_records_padded, record_words] device buffer."""
        with self._stage_lock:
            if self._db_words is None:
                telemetry = default_telemetry()
                ledger = telemetry.transfers
                with telemetry.hbm.phase("db_staging"):
                    taken = False
                    try:
                        taken = self._stage_rowmajor_delta(ledger)
                    except Exception as e:  # noqa: BLE001 - full restage
                        warnings.warn(
                            "delta row-major staging failed; staging in "
                            f"full ({str(e).splitlines()[0][:200]})"
                        )
                    if not taken:
                        host = self._host_words
                        slabs = min(8, host.shape[0] // 128)
                        if pipelined_staging_enabled() and slabs >= 2:
                            self._db_words = _stage_pieces_pipelined(
                                ledger,
                                np.array_split(host, slabs),
                                lambda parts: jnp.concatenate(
                                    parts, axis=0
                                ),
                            )
                        else:
                            self._db_words = ledger.block_until_ready(
                                ledger.device_put(
                                    host, phase="db_staging"
                                ),
                                phase="db_staging",
                            )
            return self._db_words

    def _delta_base_db(self):
        """The previous generation of a delta build, while something
        still holds it alive (serving or retired-awaiting-drain);
        None otherwise — the weakref keeps a rotation chain from
        retaining every ancestor's host image."""
        ref = self._delta_base
        return ref() if ref is not None else None

    def _stage_rowmajor_delta(self, ledger) -> bool:
        """Delta staging of the row-major buffer: scatter only this
        generation's updated rows into the base generation's resident
        device buffer. `.at[rows].set` builds a NEW buffer — the base,
        possibly still serving, is never mutated — while only the
        updated rows (plus a tiny index vector) cross the PCIe bus.
        Returns True when taken; callers fall back to full staging."""
        rows = self._delta_rows
        base = self._delta_base_db()
        if rows is None or base is None:
            return False
        base_words = base._db_words
        if base_words is None or tuple(base_words.shape) != tuple(
            self._host_words.shape
        ):
            return False
        if not rows:
            # Empty delta: generation N+1's bytes are N's exactly, and
            # jax arrays are immutable, so sharing the buffer is safe.
            self._db_words = base_words
            return True
        num_rows, width = self._host_words.shape
        if len(rows) * (width + 1) >= num_rows * width:
            # The delta touches (nearly) everything: the scattered rows
            # plus the index vector would cross the bus at full-image
            # cost or worse. Stage in full instead.
            return False
        idx = np.asarray(rows, dtype=np.int32)
        vals = np.ascontiguousarray(self._host_words[idx])
        self._db_words = ledger.block_until_ready(
            base_words.at[
                ledger.device_put(idx, phase="db_staging")
            ].set(ledger.device_put(vals, phase="db_staging")),
            phase="db_staging",
        )
        return True

    def record(self, i: int) -> bytes:
        return self._records[i]

    def prestage(
        self,
        mesh=None,
        *,
        cut_levels: int | None = None,
        bitmajor: bool = False,
        shard_axis: str = "shard",
    ) -> int:
        """Eagerly stage the serving device buffer (the double-buffer
        half of a snapshot rotation: generation N+1 moves into HBM while
        N keeps serving, so the flip itself transfers nothing).

        Without `mesh`: stages the row-major single-device buffer;
        layout variants (bit-major, bitrev, streaming) still stage
        lazily on first use — except for a **delta build**
        (`Builder.build_from`), where (a) any layout the base
        generation holds resident is rebuilt by scattering only the
        updated rows/chunks into it (a new buffer; the base is never
        mutated), and (b) the base's single-device streaming staging,
        when present, is pre-built in the same layout so the post-flip
        first request is a cache hit on the streaming tier too. Mesh
        stagings always restage in full (per-device scatter of a
        sharded buffer is not worth the choreography; documented
        limitation). Returns the bytes this call moved host->device
        (0 if everything was already resident); `last_prestage_stats`
        carries {mode, bytes_staged, bytes_full_image, bytes_saved,
        generation} for snapshots, /statusz, and the bench.
        """
        if mesh is not None:
            if cut_levels is None:
                raise ValueError("prestage(mesh=...) needs cut_levels")
            with self._stage_lock:
                key = self._streaming_key(
                    cut_levels, bitmajor, mesh, shard_axis
                )
                if (
                    self._streaming_stage is not None
                    and self._streaming_stage[0] == key
                ):
                    return 0
                self.streaming_chunks(
                    cut_levels=cut_levels,
                    bitmajor=bitmajor,
                    mesh=mesh,
                    shard_axis=shard_axis,
                )
                info = self._mesh_staging_info or {}
                staged = int(info.get("total_bytes", 0))
                self.last_prestage_stats = {
                    "mode": "full",
                    "bytes_staged": staged,
                    "bytes_full_image": staged,
                    "bytes_saved": 0,
                    "generation": int(self._generation),
                }
                return staged
        telemetry = default_telemetry()
        ledger = telemetry.transfers
        with self._stage_lock:
            bytes_before = ledger.bytes_h2d("db_staging")
            full_equiv = 0
            staged_new = False
            base = (
                self._delta_base_db()
                if self._delta_rows is not None else None
            )
            # Serve-layout double buffer for delta builds: when the
            # base generation holds a resident single-device streaming
            # staging, build ours in the same layout now (the delta
            # scatter path inside streaming_chunks) instead of leaving
            # it to the post-flip first request.
            if base is not None and self._streaming_stage is None:
                with base._stage_lock:
                    bstage = base._streaming_stage
                if bstage is not None and len(bstage[0]) == 2:
                    cut, bm = bstage[0]
                    self.streaming_chunks(cut_levels=cut, bitmajor=bm)
                    full_equiv += int(self._host_words_padded().nbytes)
                    staged_new = True
            if self._db_words is None:
                _ = self.db_words
                full_equiv += int(self._host_words.nbytes)
                staged_new = True
            if not staged_new:
                return 0
            if ledger.enabled:
                staged = max(
                    0, ledger.bytes_h2d("db_staging") - bytes_before
                )
            else:
                staged = full_equiv
            saved = max(0, full_equiv - staged)
            self.last_prestage_stats = {
                "mode": (
                    "delta"
                    if self._delta_rows is not None and saved > 0
                    else "full"
                ),
                "bytes_staged": int(staged),
                "bytes_full_image": int(full_equiv),
                "bytes_saved": int(saved),
                "generation": int(self._generation),
            }
            return int(staged)

    def release_stagings(self) -> int:
        """Drop every device staging (row-major, bit-major, bitrev,
        streaming) so a retired generation's HBM is reclaimable the
        moment its last in-flight batch drains. The host buffer stays —
        re-staging is possible but a retired snapshot normally never
        serves again. Returns the number of device buffers dropped."""
        with self._stage_lock:
            dropped = 0
            for attr in (
                "_db_words", "_db_perm", "_db_words_rev", "_db_perm_rev",
            ):
                if getattr(self, attr) is not None:
                    setattr(self, attr, None)
                    dropped += 1
            if self._streaming_stage is not None:
                self._streaming_stage = None
                dropped += 1
            self._mesh_staging_info = None
            self._host_rev = None
        # One HBM sample after the drop so the db_staging watermark and
        # live-bytes gauge reflect the reclaim without waiting for the
        # next staging to bracket a phase.
        try:
            default_telemetry().hbm.sample()
        except Exception:  # noqa: BLE001 - telemetry never raises
            pass
        return dropped

    def bitrev_block_count(self) -> int:
        """Block count of the bitrev staging: the padded power of two a
        full covering-subtree expansion emits."""
        nb = self.num_selection_blocks
        return 1 << max(0, (nb - 1).bit_length())

    def _host_words_padded(self) -> np.ndarray:
        """Host rows zero-padded to the bitrev staging's block count."""
        rows = self.bitrev_block_count() * 128
        hw = self._host_words
        if rows > hw.shape[0]:
            hw = np.concatenate(
                [hw, np.zeros((rows - hw.shape[0], hw.shape[1]),
                              np.uint32)]
            )
        return hw

    def _host_words_bitrev(self) -> np.ndarray:
        with self._stage_lock:
            if self._host_rev is None:
                from .dense_eval_planes_v2 import bitrev_block_permute_records

                self._host_rev = bitrev_block_permute_records(
                    self._host_words_padded()
                )
            return self._host_rev

    def _row_words(self, bitrev_blocks: bool = False) -> jnp.ndarray:
        """Row-major device layout (the jnp tier's input)."""
        if not bitrev_blocks:
            return self.db_words
        with self._stage_lock:
            if self._db_words_rev is None:
                telemetry = default_telemetry()
                with telemetry.hbm.phase("db_staging"):
                    self._db_words_rev = (
                        telemetry.transfers.block_until_ready(
                            telemetry.transfers.device_put(
                                self._host_words_bitrev(),
                                phase="db_staging",
                            ),
                            phase="db_staging",
                        )
                    )
                # The host-side permuted copy only exists to feed device
                # stagings; keeping it would hold a second full database
                # in host RSS for the process lifetime. (Rebuilt from
                # `_host_words` if another staging needs it.)
                self._host_rev = None
            return self._db_words_rev

    def _staged_perm(self, bitrev_blocks: bool = False) -> jnp.ndarray:
        """Bit-major layout (`permute_db_bitmajor`), staged once."""
        with self._stage_lock:
            ledger = default_telemetry().transfers
            if bitrev_blocks:
                if self._db_perm_rev is None:
                    with default_telemetry().hbm.phase("db_staging"):
                        self._db_perm_rev = ledger.block_until_ready(
                            permute_db_bitmajor(
                                ledger.device_put(
                                    self._host_words_bitrev(),
                                    phase="db_staging",
                                )
                            ),
                            phase="db_staging",
                        )
                    self._host_rev = None  # see _row_words
                return self._db_perm_rev
            if self._db_perm is None:
                with default_telemetry().hbm.phase("db_staging"):
                    self._db_perm = ledger.block_until_ready(
                        permute_db_bitmajor(
                            ledger.device_put(
                                self._host_words, phase="db_staging"
                            )
                        ),
                        phase="db_staging",
                    )
            return self._db_perm

    @staticmethod
    def _streaming_key(cut_levels, bitmajor, mesh, shard_axis):
        """Cache key for one streaming staging. Mesh stagings key on the
        device assignment + shard axis so a mesh change restages."""
        base = (int(cut_levels), bool(bitmajor))
        if mesh is None:
            return base
        fingerprint = (
            str(shard_axis),
            tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat),
        )
        return base + (fingerprint,)

    def mesh_staging_info(self) -> dict | None:
        """Per-shard detail of the live mesh staging (device id, chunk
        span, bytes, copies), or None when not mesh-staged."""
        with self._stage_lock:
            info = self._mesh_staging_info
            return dict(info) if info is not None else None

    def streaming_chunks(
        self,
        *,
        cut_levels: int,
        bitmajor: bool,
        mesh=None,
        shard_axis: str = "shard",
    ) -> jnp.ndarray:
        """Device staging for the streaming serving plan: records in
        streaming (blocked bit-reversed) block order, split into
        `2**cut_levels` chunk spans along the leading axis.

        Returns uint32[nc, chunk_records, W] row-major, or
        uint32[nc, 32, Gc, W] bit-major per chunk when `bitmajor` (the
        pallas2 scan tier). One staging is cached at a time, keyed by
        the plan split — a batch-size change that moves the planner's
        cut restages (the covering padded row count is plan-invariant,
        only the chunk boundaries move).

        With `mesh`, the chunk axis is sharded over `shard_axis`: each
        device's span of chunk spans is `jax.device_put` directly from
        the host slice to that device (no single-device detour, no
        cross-device reshard), assembled into one global array under a
        `NamedSharding`. Each per-device upload is counted in the
        TransferLedger under `db_staging`, and per-shard HBM watermarks
        land under `db_staging/dev<N>`.
        """
        from .dense_eval_planes_v2 import streaming_block_permute_records

        key = self._streaming_key(cut_levels, bitmajor, mesh, shard_axis)
        with self._stage_lock:
            if (
                self._streaming_stage is not None
                and self._streaming_stage[0] == key
            ):
                return self._streaming_stage[1]
            if mesh is not None:
                host = streaming_block_permute_records(
                    self._host_words_padded(), cut_levels
                )
                arr = self._stage_chunks_mesh(
                    host, 1 << cut_levels, mesh, shard_axis, bitmajor
                )
                self._streaming_stage = (key, arr)
                return arr
            self._mesh_staging_info = None
            ledger = default_telemetry().transfers
            with default_telemetry().hbm.phase("db_staging"):
                arr = None
                try:
                    arr = self._stage_streaming_delta(key, ledger)
                except Exception as e:  # noqa: BLE001 - full restage
                    warnings.warn(
                        "delta streaming staging failed; restaging in "
                        f"full ({str(e).splitlines()[0][:200]})"
                    )
                if arr is None:
                    arr = self._stage_streaming_full(
                        ledger, cut_levels, bitmajor
                    )
            self._streaming_stage = (key, arr)
            return arr

    def _stage_streaming_full(self, ledger, cut_levels: int,
                              bitmajor: bool):
        """Full-image staging of the single-device streaming layout —
        pipelined per-chunk when enabled (async puts, device-side
        assembly, one sync), the one-shot upfront put otherwise."""
        from .dense_eval_planes_v2 import streaming_block_permute_records

        host = streaming_block_permute_records(
            self._host_words_padded(), cut_levels
        )
        nc = 1 << cut_levels
        chunks = host.reshape(nc, -1, host.shape[1])
        if pipelined_staging_enabled() and nc >= 2:
            if bitmajor:
                from ..ops.inner_product_pallas import permute_db_bitmajor

                # stage_db_chunks_bitmajor == vmap(permute_db_bitmajor)
                # over equal chunk spans, so assembling the per-chunk
                # uploads and vmapping reproduces it bit for bit.
                return _stage_pieces_pipelined(
                    ledger, list(chunks),
                    lambda parts: jax.vmap(permute_db_bitmajor)(
                        jnp.stack(parts)
                    ),
                )
            return _stage_pieces_pipelined(ledger, list(chunks), jnp.stack)
        if bitmajor:
            from ..ops.inner_product_pallas import stage_db_chunks_bitmajor

            return ledger.block_until_ready(
                stage_db_chunks_bitmajor(
                    ledger.device_put(host, phase="db_staging"), nc
                ),
                phase="db_staging",
            )
        return ledger.block_until_ready(
            ledger.device_put(chunks, phase="db_staging"),
            phase="db_staging",
        )

    def _stage_streaming_delta(self, key, ledger):
        """Delta staging of the (non-mesh) streaming layout: upload
        only the chunks containing an updated record and scatter them
        into the base generation's resident staging (a new device
        array; the base keeps serving its own buffers). Returns the
        staged array, or None when the delta path does not apply (not
        a delta build, base released or never staged this layout, key
        mismatch)."""
        rows = self._delta_rows
        base = self._delta_base_db()
        if rows is None or base is None or len(key) != 2:
            return None
        with base._stage_lock:
            stage = base._streaming_stage
        if stage is None or stage[0] != key:
            return None
        cut_levels, bitmajor = key
        base_arr = stage[1]
        from .dense_eval_planes_v2 import streaming_block_order

        host = self._host_words_padded()
        width = host.shape[1]
        nb = host.shape[0] // 128
        levels = max(0, (nb - 1).bit_length())
        nc = 1 << cut_levels
        if nb != 1 << levels or nc > nb or int(base_arr.shape[0]) != nc:
            return None
        bpc = nb // nc
        order = streaming_block_order(levels, cut_levels)
        # Updated record i lives in natural block i // 128, which the
        # involution places at staged position order[i // 128]; staged
        # positions group into chunks of bpc consecutive blocks.
        touched = sorted({int(order[r // 128]) // bpc for r in rows})
        if not touched:
            return base_arr
        if len(touched) >= nc:
            # Every chunk holds an update: a scatter of all chunks is a
            # full-image upload plus overhead. Restage in full instead.
            return None
        blocks = host.reshape(nb, 128, width)
        pieces = np.stack([
            blocks[order[c * bpc:(c + 1) * bpc]].reshape(bpc * 128, width)
            for c in touched
        ])
        dvals = ledger.device_put(pieces, phase="db_staging")
        if bitmajor:
            from ..ops.inner_product_pallas import permute_db_bitmajor

            dvals = jax.vmap(permute_db_bitmajor)(dvals)
        didx = ledger.device_put(
            np.asarray(touched, dtype=np.int32), phase="db_staging"
        )
        return ledger.block_until_ready(
            base_arr.at[didx].set(dvals), phase="db_staging"
        )

    def _stage_chunks_mesh(self, host, nc, mesh, shard_axis, bitmajor):
        """Place chunk spans pre-partitioned over the mesh's shard axis.

        Row-major chunks [nc, chunk_records, W] go up directly. The
        bit-major layout needs the on-device permute
        (`stage_db_chunks_bitmajor`), so the row-major sharded upload is
        followed by a jitted shard-local transform constrained to the
        same shard-axis sharding — records still never cross devices.
        """
        from jax.sharding import NamedSharding, PartitionSpec

        if nc % int(mesh.shape[shard_axis]):
            raise ValueError(
                f"{nc} chunks not divisible by the {shard_axis} axis "
                f"({mesh.shape[shard_axis]} devices)"
            )
        telemetry = default_telemetry()
        ledger = telemetry.transfers
        chunks = host.reshape(nc, -1, host.shape[1])
        spec = PartitionSpec(shard_axis, None, None)
        sharding = NamedSharding(mesh, spec)
        idx_map = sharding.addressable_devices_indices_map(chunks.shape)
        pieces = []
        shards = []
        total = 0
        for dev, index in sorted(
            idx_map.items(), key=lambda kv: kv[0].id
        ):
            piece = np.ascontiguousarray(chunks[index])
            with telemetry.hbm.phase(f"db_staging/dev{dev.id}"):
                darr = jax.device_put(piece, dev)
                darr.block_until_ready()
            ledger.record_h2d(int(piece.nbytes), phase="db_staging")
            span = index[0]
            shards.append({
                "device": int(dev.id),
                "chunk_start": int(span.start or 0),
                "chunk_stop": int(
                    span.stop if span.stop is not None else nc
                ),
                "bytes": int(piece.nbytes),
                "copies": 1,
            })
            total += int(piece.nbytes)
            pieces.append(darr)
        arr = jax.make_array_from_single_device_arrays(
            chunks.shape, sharding, pieces
        )
        if bitmajor:
            from ..ops.inner_product_pallas import (
                stage_db_chunks_bitmajor,
            )

            rows = jax.jit(
                lambda x: jax.lax.with_sharding_constraint(
                    x.reshape(-1, x.shape[-1]),
                    NamedSharding(mesh, PartitionSpec(shard_axis, None)),
                )
            )(arr)
            arr = jax.jit(
                lambda x: jax.lax.with_sharding_constraint(
                    stage_db_chunks_bitmajor(x, nc),
                    NamedSharding(
                        mesh,
                        PartitionSpec(shard_axis, None, None, None),
                    ),
                )
            )(rows)
            arr = ledger.block_until_ready(arr, phase="db_staging")
        self._mesh_staging_info = {
            "shard_axis": str(shard_axis),
            "num_shards": int(mesh.shape[shard_axis]),
            "num_chunks": int(nc),
            "bitmajor": bool(bitmajor),
            "total_bytes": total,
            "copies": len(shards),
            "generation": int(self._generation),
            "shards": shards,
        }
        return arr

    def _inner_product_device(
        self, selections: jnp.ndarray, bitrev_blocks: bool = False
    ) -> jnp.ndarray:
        tier = inner_product_tier()
        inner_product = tier_inner_product(tier)
        if tier == "jnp":
            return inner_product(self._row_words(bitrev_blocks), selections)
        return inner_product(self._staged_perm(bitrev_blocks), selections)

    def inner_product_with(
        self, selections: jnp.ndarray, *, bitrev_blocks: bool = False
    ) -> List[bytes]:
        """XOR of all records whose selection bit is 1, per query.

        `selections`: uint32[num_queries, B, 4] packed blocks with
        B * 128 >= num_selection_bits. Returns one byte-string of
        `max_value_size` per query (the reference's result convention,
        `inner_product_hwy.cc:271-272`).

        With `bitrev_blocks=True` the selection blocks arrive in the
        doubling (bit-reversed) leaf order of a `bitrev_leaves=True`
        expansion, and the product runs against the bitrev-permuted
        staging — same responses, no exit gather on the expansion side.
        The block count must then equal `bitrev_block_count()` exactly.
        """
        if selections.ndim != 3 or selections.shape[-1] != 4:
            raise ValueError("selections must be uint32[nq, B, 4]")
        if bitrev_blocks:
            needed = self.bitrev_block_count()
            if selections.shape[1] != needed:
                raise ValueError(
                    f"bitrev selections must cover exactly {needed} "
                    f"blocks, got {selections.shape[1]}"
                )
        else:
            if selections.shape[1] * 128 < self.size:
                raise ValueError(
                    f"selections contain {selections.shape[1] * 128} "
                    f"bits, expected at least {self.size}"
                )
            needed = self.num_selection_blocks
            if selections.shape[1] > needed:
                selections = selections[:, :needed]
            elif selections.shape[1] < needed:
                pad = needed - selections.shape[1]
                selections = jnp.pad(
                    selections, ((0, 0), (0, pad), (0, 0))
                )
        with tracing.span("pir.inner_product", record=False):
            answers = self._inner_product_device(selections, bitrev_blocks)
        with tracing.span("pir.readback", record=False):
            out = default_telemetry().transfers.to_host(
                answers, phase="result_readback"
            )
        return words_to_record_bytes(out, out.shape[0], self._max_value_size)
