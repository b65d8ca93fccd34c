"""The unified capacity model: bytes and device-milliseconds for any
admitted unit of work.

Two planners used to carry private copies of the same HBM arithmetic:

* dense PIR (`pir/planner.py`): selection-attributable bytes per tier —
  materialized ``num_keys * eff_blocks * 16``, streaming
  ``num_keys * 16 * (2**cut_levels + 2 * 2**chunk_levels)`` (cut-state
  plus a double-buffered chunk), chunked
  ``num_keys * 2**chunk_expand_levels * 16`` — against the
  ``DPF_TPU_SELECTION_BYTES_BUDGET`` budget (default 1 GiB);
* heavy hitters (`heavy_hitters/aggregator.plan_level`): per-lane live
  bytes ``16 * (walk_levels + value_blocks + 3)`` against the
  ``DPF_TPU_HH_BYTES_BUDGET`` budget (default 256 MiB).

Those formulas now live HERE, once, as methods of `CapacityModel`;
the planners are thin clients. On top of the byte model the capacity
model adds a *time* model: measured per-tier throughput (loaded from
the perf-gate trajectory `benchmarks/results/history.jsonl`, newest
clean record per metric, conservative built-in fallbacks when no
history exists) prices work in estimated device-milliseconds — the
quantity serving admission reasons about (estimated queue drain time
vs. request deadline, see `capacity/admission.py`).

Since the cost-model accuracy ledger (`observability/costmodel.py`)
landed, the time model is *validated and corrected*: an installed
correction provider (see `capacity/recalibrate.py`) multiplies each
priced device-ms by a clamped per-(workload, shape-bucket) EWMA
factor learned from measured residuals, and a `DPF_TPU_COSTMODEL_MISPRICE`
override (failpoint-style, e.g. ``pir=3.0``) deliberately misprices a
workload so drift detection and recalibration can be drilled end to
end without touching real throughput numbers.

Environment knobs: ``DPF_TPU_SELECTION_BYTES_BUDGET``,
``DPF_TPU_HH_BYTES_BUDGET`` (byte budgets, unchanged semantics),
``DPF_TPU_DEVICE_MEMORY_BYTES`` (pins the device memory the budgets
derive from when no explicit budget is set),
``DPF_TPU_CAPACITY_HISTORY`` (alternate history.jsonl path),
``DPF_TPU_CALIBRATION_STALE_S`` (newest-clean-record age beyond which
calibration reports itself stale), ``DPF_TPU_COSTMODEL_MISPRICE``
(per-workload synthetic estimate multiplier for accuracy drills).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import time
from typing import Callable, Dict, Optional

_SELECTION_BLOCK_BYTES = 16
_HH_BLOCK_BYTES = 16
_DEFAULT_SELECTION_BUDGET = 1 << 30  # 1 GiB
_DEFAULT_FRONTIER_BUDGET = 1 << 28  # 256 MiB
# When no explicit budget or env override is given but the device
# memory is known, selection tensors get 1/16 of it (the database, cut
# states, and runtime scratch share the rest) and the heavy-hitters
# frontier 1/64 — chosen so a 16 GiB v5e chip derives exactly the
# historical fixed defaults (1 GiB / 256 MiB).
_SELECTION_MEMORY_FRACTION = 16
_FRONTIER_MEMORY_FRACTION = 64

# Conservative built-in throughput fallbacks, used only when the
# history store has no clean record for the metric. Sourced from the
# committed TPU v5e captures (see ROADMAP "Recent"), derated 2x so an
# uncalibrated process over-sheds rather than over-admits.
_FALLBACK_THROUGHPUT = {
    "serving_closed_loop_queries_per_sec": 1300.0,
    "heavy_hitters_sweep_lanes_per_sec": 950_000.0,
}

# History metrics that calibrate each unit of work.
_SERVING_QPS_METRIC = "serving_closed_loop_queries_per_sec"
_HH_LANES_METRIC = "heavy_hitters_sweep_lanes_per_sec"

# Calibration staleness: the bench history is appended per perf-gated
# PR, so a newest clean record older than this is a process pricing
# work off a stale machine state.
_DEFAULT_STALE_AFTER_S = 30 * 86400.0
_STALE_ENV = "DPF_TPU_CALIBRATION_STALE_S"

# Failpoint-style synthetic mispricing: "workload=factor[,workload=
# factor]" multiplies the device-ms estimate for that workload. Parsed
# lazily and cached per env value so the disarmed path is one dict hit.
_MISPRICE_ENV = "DPF_TPU_COSTMODEL_MISPRICE"
_misprice_cache: tuple = ("", {})


def misprice_factor(workload: str) -> float:
    """The armed synthetic estimate multiplier for `workload` (1.0
    disarmed). Read live so tests and the presubmit drill can toggle
    the env without rebuilding the model."""
    global _misprice_cache
    raw = os.environ.get(_MISPRICE_ENV, "").strip()
    cached_raw, table = _misprice_cache
    if raw != cached_raw:
        table = {}
        for item in raw.split(","):
            item = item.strip()
            if not item:
                continue
            key, _, value = item.partition("=")
            try:
                table[key.strip()] = float(value)
            except ValueError:
                continue
        _misprice_cache = (raw, table)
    return table.get(workload, 1.0)


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return max(1, int(raw))
    except ValueError:
        return None


def default_history_path() -> str:
    """The perf-gate trajectory this repo commits; overridable for
    deployments whose history lives elsewhere."""
    env = os.environ.get("DPF_TPU_CAPACITY_HISTORY", "").strip()
    if env:
        return env
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(repo_root, "benchmarks", "results", "history.jsonl")


class ThroughputCalibration:
    """Measured per-metric throughput from the bench history store.

    Reads `history.jsonl` once, lazily, keeping the newest *clean*
    (`status == "ok"`, finite value) record per metric — the same
    cleanliness rule the regression gate applies, so `infra_error` and
    `last_good` echoes (a harness-failure run's shape) never
    calibrate anything; they are counted per status instead. Missing
    file, malformed lines, and absent metrics all degrade to the
    built-in conservative fallbacks — journaled once per metric as
    `capacity.calibration_fallback`, because an uncalibrated process
    over-sheds; calibration must never take serving down.

    The winning record's `ts_unix` is retained so `/statusz` and
    `/capacityz` can show record age and an explicit `stale` flag when
    the newest clean record is older than `stale_after_s`.
    """

    def __init__(
        self,
        history_path: Optional[str] = None,
        stale_after_s: Optional[float] = None,
    ):
        self._path = history_path or default_history_path()
        if stale_after_s is None:
            raw = os.environ.get(_STALE_ENV, "").strip()
            try:
                stale_after_s = float(raw) if raw else _DEFAULT_STALE_AFTER_S
            except ValueError:
                stale_after_s = _DEFAULT_STALE_AFTER_S
        self.stale_after_s = stale_after_s
        self._lock = threading.Lock()
        self._loaded = False
        self._newest: Dict[str, float] = {}
        self._ts: Dict[str, float] = {}
        self._skipped: Dict[str, int] = {}
        self._fallback_noted: set = set()

    def _load(self) -> None:
        with self._lock:
            if self._loaded:
                return
            self._loaded = True
            try:
                with open(self._path) as f:
                    lines = f.readlines()
            except OSError:
                return
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(rec, dict):
                    continue
                value = rec.get("value")
                status = str(rec.get("status", "ok"))
                if (
                    status == "ok"
                    and isinstance(value, (int, float))
                    and math.isfinite(float(value))
                    and float(value) > 0
                ):
                    # File order is append order: last clean wins,
                    # whatever device/topology/git_rev stamp it carries.
                    metric = str(rec.get("metric"))
                    self._newest[metric] = float(value)
                    ts = rec.get("ts_unix")
                    if isinstance(ts, (int, float)) and math.isfinite(
                        float(ts)
                    ):
                        self._ts[metric] = float(ts)
                    else:
                        self._ts.pop(metric, None)
                else:
                    self._skipped[status] = self._skipped.get(status, 0) + 1

    def lookup(self, metric: str) -> Optional[float]:
        """Newest clean measurement for `metric`, or None."""
        self._load()
        return self._newest.get(metric)

    def throughput(self, metric: str, fallback: float) -> float:
        value = self.lookup(metric)
        if value is not None:
            return value
        self._note_fallback(metric, fallback)
        return fallback

    def _note_fallback(self, metric: str, fallback: float) -> None:
        """Journal the first fall-back to the conservative built-in for
        each metric — the operator-visible sign that admission is
        pricing work off a guess, not a measurement."""
        with self._lock:
            if metric in self._fallback_noted:
                return
            self._fallback_noted.add(metric)
        from ..observability import events as events_mod

        events_mod.emit(
            "capacity.calibration_fallback",
            message=(
                f"no clean history record for {metric}; pricing with "
                f"conservative fallback {fallback:g}"
            ),
            severity="warning",
            metric=metric,
            fallback=fallback,
            history_path=self._path,
        )

    def record_age_s(self, metric: str) -> Optional[float]:
        """Age of the winning clean record, or None without one (or
        when the record carried no timestamp)."""
        self._load()
        with self._lock:
            ts = self._ts.get(metric)
        return None if ts is None else max(0.0, time.time() - ts)

    def stale(self, metric: str) -> bool:
        """True when `metric` has no clean record at all, or its newest
        clean record is older than `stale_after_s`."""
        self._load()
        with self._lock:
            if metric not in self._newest:
                return True
            ts = self._ts.get(metric)
        if ts is None:
            # A clean record without a timestamp cannot be aged; treat
            # it as fresh rather than permanently stale.
            return False
        return (time.time() - ts) > self.stale_after_s

    def export(self) -> dict:
        self._load()
        now = time.time()
        with self._lock:
            newest = dict(sorted(self._newest.items()))
            ts = dict(self._ts)
            skipped = dict(sorted(self._skipped.items()))
        metrics = {}
        any_stale = False
        for metric, value in newest.items():
            age = None if metric not in ts else max(0.0, now - ts[metric])
            is_stale = age is not None and age > self.stale_after_s
            any_stale = any_stale or is_stale
            metrics[metric] = {
                "value": value,
                "ts_unix": ts.get(metric),
                "age_s": None if age is None else round(age, 1),
                "stale": is_stale,
            }
        return {
            "history_path": self._path,
            "calibrated_metrics": newest,
            "metrics": metrics,
            "skipped_records": skipped,
            "stale_after_s": self.stale_after_s,
            "stale": any_stale,
        }


@dataclasses.dataclass(frozen=True)
class WorkCost:
    """Price of one admitted unit of work."""

    bytes_peak: int  # modeled peak live HBM bytes while it runs
    device_ms: float  # estimated device milliseconds to serve it
    quantity: int  # how many atoms (keys, lanes) it contains
    unit: str  # "pir_keys" | "hh_lanes"


@dataclasses.dataclass(frozen=True)
class LevelChunking:
    """Resolved prefix chunking for one heavy-hitters level."""

    chunk_prefixes: int  # power of two
    num_chunks: int
    lane_bytes: int
    bytes_peak: int  # modeled peak for one chunk
    budget_bytes: int


class CapacityModel:
    """Prices any admitted unit of work in bytes and device-ms.

    One instance per process is the normal deployment
    (`default_capacity_model()`); tests construct their own with pinned
    budgets and calibration so the model is deterministic.
    """

    def __init__(
        self,
        device_memory_bytes: Optional[int] = None,
        selection_budget: Optional[int] = None,
        frontier_budget: Optional[int] = None,
        calibration: Optional[ThroughputCalibration] = None,
    ):
        if device_memory_bytes is None:
            device_memory_bytes = _env_int("DPF_TPU_DEVICE_MEMORY_BYTES")
        if device_memory_bytes is None:
            from ..observability.device import (
                device_memory_bytes as probe_device_memory,
            )

            # None on CPU/uninitialized JAX: the byte budgets then fall
            # back to their historical fixed defaults.
            device_memory_bytes = probe_device_memory()
        self._device_memory = device_memory_bytes
        self._selection_budget = selection_budget
        self._frontier_budget = frontier_budget
        self.calibration = (
            calibration if calibration is not None else ThroughputCalibration()
        )
        # Optional `fn(workload, quantity) -> factor` multiplying each
        # priced device-ms (see capacity/recalibrate.py). None (the
        # default, and the kill-switch end state) prices raw.
        self._correction: Optional[Callable[[str, int], float]] = None
        # Serving-mesh shape (shard_devices, key_devices), or None for
        # single-device pricing. serving/ configures this when a session
        # binds a 2-D mesh so admission and brownout price per-shard
        # work and per-mesh throughput without any of their own changes.
        self._mesh_shape: Optional[tuple] = None
        # Stable replica identity stamped onto price exports so a fleet
        # front door can read many models side by side (fleet/router.py
        # spreads tenants by these prices). None outside a fleet.
        self._replica: Optional[str] = None

    # -- replica identity ----------------------------------------------------

    def set_replica(self, replica_id: Optional[str]) -> None:
        """Label this model's exports with a fleet replica id (None
        clears it)."""
        self._replica = str(replica_id) if replica_id is not None else None

    @property
    def replica_id(self) -> Optional[str]:
        return self._replica

    def price_export(
        self, num_keys: int = 8, num_blocks: Optional[int] = None
    ) -> dict:
        """The per-replica price card the fleet front door routes by:
        one `price_pir_keys` probe at a small fixed batch, normalized
        per key, plus the calibrated throughput it derives from. Cheap
        enough to call per routing decision (no device work — pure
        calibration arithmetic)."""
        cost = self.price_pir_keys(num_keys, num_blocks)
        return {
            "replica": self._replica,
            "probe_keys": int(num_keys),
            "device_ms": round(cost.device_ms, 4),
            "device_ms_per_key": round(
                cost.device_ms / max(1, num_keys), 5
            ),
            "bytes_peak": cost.bytes_peak,
            "queries_per_sec": round(self.serving_queries_per_sec(), 2),
        }

    # -- serving mesh --------------------------------------------------------

    def configure_mesh(
        self,
        shard_devices: Optional[int],
        key_devices: Optional[int] = None,
    ) -> None:
        """Declare the serving mesh shape (None clears it). With a mesh
        configured, byte prices are per shard — each device holds only
        its chunk span of the cut state — and throughput prefers the
        calibrated multi-device `serving_qps_{ndev}dev` metric."""
        if shard_devices is None:
            self._mesh_shape = None
            return
        self._mesh_shape = (int(shard_devices), int(key_devices or 1))

    @property
    def mesh_shape(self) -> Optional[tuple]:
        return self._mesh_shape

    def mesh_device_count(self) -> int:
        if self._mesh_shape is None:
            return 1
        return self._mesh_shape[0] * self._mesh_shape[1]

    def mesh_selection_budget_bytes(self) -> int:
        """Per-mesh selection budget: every device brings its own HBM
        slice, so the mesh-wide budget is the per-device budget times
        the device count (per-shard peaks are still gated against the
        per-device `selection_budget_bytes`)."""
        return self.selection_budget_bytes() * self.mesh_device_count()

    def streaming_selection_bytes_per_shard(
        self, num_keys: int, cut_levels: int, chunk_levels: int
    ) -> int:
        """Per-device streaming peak on the mesh: each device expands
        its key slice down to the cut, keeps only its span of cut-state
        lanes, and double-buffers one chunk's selections."""
        if self._mesh_shape is None:
            return self.streaming_selection_bytes(
                num_keys, cut_levels, chunk_levels
            )
        shards, key_devices = self._mesh_shape
        local_keys = -(-num_keys // max(1, key_devices))
        local_lanes = max(1, (1 << cut_levels) // max(1, shards))
        return local_keys * _SELECTION_BLOCK_BYTES * (
            local_lanes + 2 * (1 << chunk_levels)
        )

    def mesh_pir_bytes_per_shard(
        self, num_keys: int, num_blocks: int
    ) -> int:
        """Modeled per-device HBM peak for one mesh-served PIR batch
        (streaming split re-derived the way the serving plan derives
        it: cut covers at least the shard axis)."""
        if self._mesh_shape is None:
            return self.materialized_selection_bytes(num_keys, num_blocks)
        shards, key_devices = self._mesh_shape
        expand = max(0, (num_blocks - 1).bit_length())
        s_levels = max(0, (shards - 1).bit_length())
        local_keys = -(-num_keys // max(1, key_devices))
        chunk = min(
            self.pick_streaming_split(local_keys, expand),
            max(0, expand - s_levels),
        )
        return self.streaming_selection_bytes_per_shard(
            num_keys, expand - chunk, chunk
        )

    def set_correction_provider(
        self, provider: Optional[Callable[[str, int], float]]
    ) -> None:
        """Install (or, with None, remove) the recalibration correction
        provider. Providers must be cheap and must not raise; a raising
        provider is ignored for that price."""
        self._correction = provider

    def _corrected(self, workload: str, quantity: int, device_ms: float):
        """Apply the synthetic misprice override and any installed
        correction factor to a raw device-ms estimate."""
        device_ms *= misprice_factor(workload)
        provider = self._correction
        if provider is not None:
            try:
                device_ms *= float(provider(workload, quantity))
            except Exception:  # noqa: BLE001 - pricing must never raise
                pass
        return device_ms

    # -- budgets -------------------------------------------------------------

    @property
    def device_memory_bytes(self) -> Optional[int]:
        return self._device_memory

    def selection_budget_bytes(self) -> int:
        """HBM budget for selection-attributable tensors: env override,
        then explicit construction, then a fraction of known device
        memory, then the historical 1 GiB default."""
        env = _env_int("DPF_TPU_SELECTION_BYTES_BUDGET")
        if env is not None:
            return env
        if self._selection_budget is not None:
            return max(1, int(self._selection_budget))
        if self._device_memory is not None:
            return max(1, self._device_memory // _SELECTION_MEMORY_FRACTION)
        return _DEFAULT_SELECTION_BUDGET

    def frontier_budget_bytes(self) -> int:
        """Byte budget for one fused heavy-hitters level evaluation."""
        env = _env_int("DPF_TPU_HH_BYTES_BUDGET")
        if env is not None:
            return env
        if self._frontier_budget is not None:
            return max(1, int(self._frontier_budget))
        if self._device_memory is not None:
            return max(1, self._device_memory // _FRONTIER_MEMORY_FRACTION)
        return _DEFAULT_FRONTIER_BUDGET

    # -- dense-PIR selection bytes (the pir/planner byte model) --------------

    def materialized_selection_bytes(
        self, num_keys: int, eff_blocks: int
    ) -> int:
        """Full selection matrix live at once: one 128-bit block per
        (key, block)."""
        return num_keys * eff_blocks * _SELECTION_BLOCK_BYTES

    def streaming_selection_bytes(
        self, num_keys: int, cut_levels: int, chunk_levels: int
    ) -> int:
        """Cut-state seeds for the whole scan plus one double-buffered
        chunk's selections (factor 2: XLA prefetches the next database
        span while the current chunk multiplies)."""
        return num_keys * _SELECTION_BLOCK_BYTES * (
            (1 << cut_levels) + 2 * (1 << chunk_levels)
        )

    def chunked_selection_bytes(
        self, num_keys: int, chunk_expand_levels: int
    ) -> int:
        """Legacy chunked loop: one chunk's selections at a time."""
        return num_keys * (1 << chunk_expand_levels) * _SELECTION_BLOCK_BYTES

    def pick_streaming_split(
        self,
        num_keys: int,
        expand_levels: int,
        budget_bytes: Optional[int] = None,
    ) -> int:
        """Largest chunk_levels whose modeled streaming peak fits the
        budget (bigger chunks amortize per-step overhead); if no split
        fits, the peak-minimizing split (near
        ``(expand_levels - 1) / 2``)."""
        budget = (
            self.selection_budget_bytes()
            if budget_bytes is None
            else budget_bytes
        )
        feasible = [
            r
            for r in range(expand_levels + 1)
            if self.streaming_selection_bytes(num_keys, expand_levels - r, r)
            <= budget
        ]
        if feasible:
            return max(feasible)
        return min(
            range(expand_levels + 1),
            key=lambda r: self.streaming_selection_bytes(
                num_keys, expand_levels - r, r
            ),
        )

    def pick_chunked_expand_levels(
        self,
        num_keys: int,
        expand_levels: int,
        granule_levels: int,
        budget_bytes: Optional[int] = None,
    ) -> int:
        """Largest chunk_expand_levels (capped at the MXU-friendly
        granule) whose one-chunk selections fit the budget; floor 0."""
        budget = (
            self.selection_budget_bytes()
            if budget_bytes is None
            else budget_bytes
        )
        cel = min(expand_levels, granule_levels)
        while cel > 0 and self.chunked_selection_bytes(num_keys, cel) > budget:
            cel -= 1
        return cel

    # -- heavy-hitters frontier bytes (the aggregator byte model) ------------

    def hh_lane_bytes(self, walk_levels: int, value_blocks: int) -> int:
        """Modeled live bytes per (key, prefix) lane of one fused level:
        the walk state, the repeated correction words for the levels
        walked, the path, and the leaf value blocks (+3 covers seeds
        in/out and the path)."""
        return _HH_BLOCK_BYTES * (walk_levels + value_blocks + 3)

    def plan_hh_level(
        self,
        num_keys: int,
        num_prefixes: int,
        walk_levels: int,
        value_blocks: int,
        budget_bytes: Optional[int] = None,
    ) -> LevelChunking:
        """Largest power-of-two prefix chunk whose modeled bytes fit
        the budget (bigger chunks amortize dispatch); floor of one
        prefix. Chunked evaluation is bit-identical to unchunked
        because lanes are independent."""
        budget = (
            self.frontier_budget_bytes()
            if budget_bytes is None
            else budget_bytes
        )
        lb = self.hh_lane_bytes(walk_levels, value_blocks)
        chunk = 1 << max(0, (max(1, num_prefixes) - 1).bit_length())
        while chunk > 1 and num_keys * chunk * lb > budget:
            chunk //= 2
        return LevelChunking(
            chunk_prefixes=chunk,
            num_chunks=-(-num_prefixes // chunk),
            lane_bytes=lb,
            bytes_peak=num_keys * chunk * lb,
            budget_bytes=budget,
        )

    # -- time model ----------------------------------------------------------

    def serving_queries_per_sec(self) -> float:
        """Calibrated end-to-end serving throughput (queries/s) — the
        denominator of admission's queue-drain estimate.

        With a mesh configured: the calibrated
        `serving_qps_{ndev}dev` record wins when one exists; otherwise
        the single-device calibration scales by the device count (the
        near-linear-scaling prior, replaced the first time the
        multi-device bench stage lands a record)."""
        ndev = self.mesh_device_count()
        if ndev > 1:
            value = self.calibration.lookup(f"serving_qps_{ndev}dev")
            if value is not None:
                return value
            return ndev * self.calibration.throughput(
                _SERVING_QPS_METRIC,
                _FALLBACK_THROUGHPUT[_SERVING_QPS_METRIC],
            )
        return self.calibration.throughput(
            _SERVING_QPS_METRIC,
            _FALLBACK_THROUGHPUT[_SERVING_QPS_METRIC],
        )

    def hh_lanes_per_sec(self) -> float:
        return self.calibration.throughput(
            _HH_LANES_METRIC, _FALLBACK_THROUGHPUT[_HH_LANES_METRIC]
        )

    def price_pir_keys(
        self, num_keys: int, num_blocks: Optional[int] = None
    ) -> WorkCost:
        """Price a serving request of `num_keys` DPF keys. The byte
        peak assumes the materialized tier when the database geometry
        is known (the most HBM-hungry tier the planner could pick);
        device-ms comes from calibrated serving throughput."""
        qps = max(1e-6, self.serving_queries_per_sec())
        if not num_blocks:
            bytes_peak = 0
        elif self._mesh_shape is not None:
            bytes_peak = self.mesh_pir_bytes_per_shard(num_keys, num_blocks)
        else:
            bytes_peak = self.materialized_selection_bytes(
                num_keys, num_blocks
            )
        return WorkCost(
            bytes_peak=bytes_peak,
            device_ms=self._corrected("pir", num_keys, num_keys * 1e3 / qps),
            quantity=num_keys,
            unit="pir_keys",
        )

    def price_sparse_pir_keys(
        self, num_keys: int, num_blocks: Optional[int] = None
    ) -> WorkCost:
        """Price a sparse (cuckoo key-value) serving request of
        `num_keys` DPF keys over the bucket space. One expansion
        produces one selection matrix, reused by **two** dense inner
        products (parallel key and value stores over the same `1.5×n`
        buckets) — so the byte peak matches the dense price for the
        bucket geometry while device-ms doubles the per-key work. The
        correction loop keys on its own "sparse" workload so dense
        recalibration never skews sparse admission."""
        qps = max(1e-6, self.serving_queries_per_sec())
        if not num_blocks:
            bytes_peak = 0
        elif self._mesh_shape is not None:
            bytes_peak = self.mesh_pir_bytes_per_shard(num_keys, num_blocks)
        else:
            bytes_peak = self.materialized_selection_bytes(
                num_keys, num_blocks
            )
        return WorkCost(
            bytes_peak=bytes_peak,
            device_ms=self._corrected(
                "sparse", num_keys, 2.0 * num_keys * 1e3 / qps
            ),
            quantity=num_keys,
            unit="sparse_keys",
        )

    def price_hh_level(
        self,
        num_keys: int,
        num_prefixes: int,
        walk_levels: int,
        value_blocks: int,
    ) -> WorkCost:
        """Price one heavy-hitters level chunk (`num_keys x
        num_prefixes` lanes)."""
        chunking = self.plan_hh_level(
            num_keys, num_prefixes, walk_levels, value_blocks
        )
        lanes = num_keys * num_prefixes
        lps = max(1e-6, self.hh_lanes_per_sec())
        return WorkCost(
            bytes_peak=chunking.bytes_peak,
            device_ms=self._corrected("hh", lanes, lanes * 1e3 / lps),
            quantity=lanes,
            unit="hh_lanes",
        )

    def export(self) -> dict:
        """The /statusz view of the model."""
        out = {
            "replica": self._replica,
            "device_memory_bytes": self._device_memory,
            "selection_budget_bytes": self.selection_budget_bytes(),
            "frontier_budget_bytes": self.frontier_budget_bytes(),
            "serving_queries_per_sec": round(
                self.serving_queries_per_sec(), 2
            ),
            "hh_lanes_per_sec": round(self.hh_lanes_per_sec(), 2),
            "calibration": self.calibration.export(),
        }
        if self._mesh_shape is not None:
            out["mesh"] = {
                "shard_devices": self._mesh_shape[0],
                "key_devices": self._mesh_shape[1],
                "devices": self.mesh_device_count(),
                "mesh_selection_budget_bytes": (
                    self.mesh_selection_budget_bytes()
                ),
            }
        return out


_default_model: Optional[CapacityModel] = None
_default_lock = threading.Lock()


def default_capacity_model() -> CapacityModel:
    """The process-wide model every planner delegates to by default."""
    global _default_model
    with _default_lock:
        if _default_model is None:
            _default_model = CapacityModel()
        return _default_model


def set_default_capacity_model(
    model: Optional[CapacityModel],
) -> Optional[CapacityModel]:
    """Swap the process-wide model (tests; None restores lazy default).
    Returns the previous model."""
    global _default_model
    with _default_lock:
        previous = _default_model
        _default_model = model
        return previous
