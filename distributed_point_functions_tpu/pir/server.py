"""PIR servers: role logic (Plain / Leader / Helper) and the dense server.

`DpfPirServer` reimplements the deployment-role superclass of
`pir/dpf_pir_server.h:42-65`, `.cc:30-193`:

* **Plain** answers unencrypted requests directly.
* **Leader** receives a `LeaderRequest`, forwards the encrypted helper
  request through an injected `sender` callback while computing its own
  response in the `while_waiting` callback, then XOR-combines both masked
  responses.
* **Helper** decrypts its request via an injected `decrypter` callback,
  computes the response, and masks it with an AES-CTR one-time pad expanded
  from the client's seed.

Transport and encryption stay injected callbacks (the reference's
`ForwardHelperRequestFn` / `DecryptHelperRequestFn` seam,
`pir/dpf_pir_server.h:92-109`), so any RPC stack and hybrid-encryption
scheme plug in unchanged.

`DenseDpfPirServer` (`pir/dense_dpf_pir_server.h:32-74`) binds the role
logic to the dense database: each request's DPF keys are evaluated in one
fused, batched TPU pipeline (`dense_eval.py`) and pushed through the XOR
inner product.
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Optional

from ..dpf import DistributedPointFunction, DpfParameters
from ..observability import events as events_mod
from ..observability import tracing
from ..observability import phases as phases_mod
from ..observability.device import default_telemetry, shape_key
from ..prng import Aes128CtrSeededPrng, xor_bytes
from ..value_types import XorType
from . import messages
from .database import DenseDpfPirDatabase, words_to_record_bytes
from .dense_eval import (
    donation_enabled,
    evaluate_selection_blocks,
    evaluate_selection_blocks_donated,
    serving_expansion,
    stage_keys,
    stage_keys_host,
    stage_keys_walked,
)
from .planner import (
    ServingPlan,
    plan_dense_serving,
    selection_budget_bytes,
    streaming_ip,
)

# sender(helper_request: PirRequest, while_waiting: Callable[[], None])
#   -> PirResponse
ForwardHelperRequestFn = Callable[..., "messages.PirResponse"]
# decrypter(ciphertext: bytes, context_info: bytes) -> bytes
DecryptHelperRequestFn = Callable[[bytes, bytes], bytes]

ENCRYPTION_CONTEXT_INFO = b"DpfPirServer"


# -- process-wide brownout tier floor ----------------------------------------
#
# The brownout ladder (`capacity/brownout.py`, wired by serving's
# `attach_brownout`) forces *every* server in the process to plan at or
# below a tier: cheaper tiers shrink peak HBM so concurrent sweeps and
# serving stop fighting for memory under SLO burn. 0 = unconstrained.
# Per-shape OOM demotion (`_tier_floor`) composes with it — the planner
# honors whichever floor is lower-tier.

_GLOBAL_TIER_FLOOR = 0
_GLOBAL_TIER_FLOOR_LOCK = threading.Lock()


def set_tier_floor(mode: Optional[str]) -> None:
    """Force every dense-PIR plan in this process to `mode` or cheaper
    ("materialized" / "streaming" / "chunked"); None or "materialized"
    clears the constraint."""
    global _GLOBAL_TIER_FLOOR
    tiers = DenseDpfPirServer._TIERS
    floor = 0 if mode is None else tiers.index(mode)
    with _GLOBAL_TIER_FLOOR_LOCK:
        _GLOBAL_TIER_FLOOR = floor
    tracing.runtime_counters.inc(
        f"pir.tier_floor.{'cleared' if floor == 0 else mode}"
    )


def clear_tier_floor() -> None:
    set_tier_floor(None)


def tier_floor() -> str:
    return DenseDpfPirServer._TIERS[_GLOBAL_TIER_FLOOR]


class DpfPirServer:
    """Role dispatch shared by all DPF-based PIR servers."""

    def __init__(self):
        self._role = "plain"
        self._sender: Optional[ForwardHelperRequestFn] = None
        self._decrypter: Optional[DecryptHelperRequestFn] = None
        self._encryption_context_info = ENCRYPTION_CONTEXT_INFO
        self._plain_handler: Optional[Callable] = None

    # -- role setup ---------------------------------------------------------

    def make_leader(self, sender: ForwardHelperRequestFn) -> None:
        if sender is None:
            raise ValueError("sender may not be None")
        self._sender = sender
        self._role = "leader"

    def make_helper(
        self,
        decrypter: DecryptHelperRequestFn,
        encryption_context_info: bytes = ENCRYPTION_CONTEXT_INFO,
    ) -> None:
        if decrypter is None:
            raise ValueError("decrypter may not be None")
        self._decrypter = decrypter
        self._encryption_context_info = encryption_context_info
        self._role = "helper"

    @property
    def role(self) -> str:
        return self._role

    def set_plain_handler(self, handler: Optional[Callable]) -> None:
        """Batch-entry hook: route every internal plain evaluation (the
        plain role's requests, the Leader's own share inside
        `while_waiting`, the Helper's decrypted request) through
        `handler(request) -> PirResponse` instead of calling
        `handle_plain_request` directly. `serving/` installs its dynamic
        batcher here; `None` restores the direct path. The handler may
        itself call `handle_plain_request` (the real evaluator) — the
        hook only intercepts the role-dispatch call sites."""
        self._plain_handler = handler

    def _dispatch_plain(self, request):
        if self._plain_handler is not None:
            return self._plain_handler(request)
        return self.handle_plain_request(request)

    def get_public_params(self):
        """`PirServerPublicParams` proto to send to a client before any
        queries (`pir/pir_server.h:31`, `dense_dpf_pir_server.cc:87-89`).
        The dense server has none, so the base returns the empty message;
        the sparse server fills in its cuckoo params."""
        from .. import serialization

        return serialization.public_params_to_proto(None)

    # -- request handling ---------------------------------------------------

    def handle_request(
        self, request: "messages.PirRequest"
    ) -> "messages.PirResponse":
        if self._role == "plain":
            return self._dispatch_plain(request)
        if self._role == "leader":
            return self._handle_leader_request(request)
        return self._handle_helper_request(request)

    def handle_plain_request(self, request):
        raise NotImplementedError

    def _parse_helper_request(self, data: bytes) -> "messages.HelperRequest":
        """Decode the decrypted helper request (subclass knows the DPF)."""
        raise NotImplementedError

    def _handle_leader_request(self, request):
        if request.leader_request is None:
            raise ValueError("request must be a valid LeaderRequest")
        leader_request = request.leader_request
        if leader_request.plain_request is None:
            raise ValueError("plain_request must be set")
        if leader_request.encrypted_helper_request is None:
            raise ValueError("encrypted_helper_request must be set")

        plain_request = messages.PirRequest(
            plain_request=leader_request.plain_request
        )
        helper_request = messages.PirRequest(
            encrypted_helper_request=leader_request.encrypted_helper_request
        )

        # The sender must invoke while_waiting (which computes the leader's
        # own share) — detect misbehaving senders like the reference does
        # (`dpf_pir_server.cc:111-115`).
        state = {"has_run": False, "response": None, "error": None}

        def while_waiting():
            try:
                state["response"] = self._dispatch_plain(plain_request)
            except Exception as e:  # surfaced after the sender returns
                state["error"] = e
            state["has_run"] = True

        helper_response = self._sender(helper_request, while_waiting)
        if not state["has_run"]:
            raise RuntimeError(
                "handle_request: while_waiting was not called from the "
                "sender passed at construction"
            )
        if state["error"] is not None:
            raise state["error"]
        leader_response = state["response"]

        hr = helper_response.dpf_pir_response.masked_response
        lr = leader_response.dpf_pir_response.masked_response
        if len(hr) != len(lr):
            raise RuntimeError(
                f"number of responses from Helper (={len(hr)}) does not "
                f"match the number of responses from Leader (={len(lr)})"
            )
        with tracing.span("combine"):
            combined = []
            for i, (h, l) in enumerate(zip(hr, lr)):
                if len(h) != len(l):
                    raise RuntimeError(
                        f"response size mismatch at index {i}: got {len(h)} "
                        f"(Helper) vs. {len(l)} (Leader)"
                    )
                combined.append(xor_bytes(h, l))
        return messages.PirResponse(
            dpf_pir_response=messages.DpfPirResponse(masked_response=combined)
        )

    def _handle_helper_request(self, request):
        if request.encrypted_helper_request is None:
            raise ValueError("request must be a valid EncryptedHelperRequest")
        with tracing.span("helper_decrypt"):
            decrypted = self._decrypter(
                request.encrypted_helper_request.encrypted_request,
                self._encryption_context_info,
            )
            inner = self._parse_helper_request(decrypted)
        response = self._dispatch_plain(
            messages.PirRequest(plain_request=inner.plain_request)
        )
        with tracing.span("mask"):
            prng = Aes128CtrSeededPrng(inner.one_time_pad_seed)
            masked = [
                xor_bytes(r, prng.get_random_bytes(len(r)))
                for r in response.dpf_pir_response.masked_response
            ]
        return messages.PirResponse(
            dpf_pir_response=messages.DpfPirResponse(masked_response=masked)
        )


class DenseDpfPirServer(DpfPirServer):
    """PIR over a dense index space (`pir/dense_dpf_pir_server.h:32`).

    Pass a `jax.sharding.Mesh` to serve across chips: the database is
    record-sharded over the mesh and every request runs the sharded
    expand+inner-product step (`parallel/sharded.py`) with XLA collectives
    over ICI; without a mesh, requests run the single-device fused
    pipeline (with the Pallas MXU inner product on TPU).
    """

    def __init__(self, database: DenseDpfPirDatabase, mesh=None):
        super().__init__()
        if database is None:
            raise ValueError("database cannot be None")
        if database.size <= 0:
            raise ValueError("database must not be empty")
        self._database = database
        self._mesh = mesh
        self._sharded_step = None
        self._sharded_db = None
        # 2-D mesh serving plan (shard axis x key axis): built lazily on
        # the first request (a build failure raises); a device OOM parks
        # the error here and the OOM demotion serves single-device for
        # the rest of the process.
        self._mesh_plan = None
        self._mesh_db = None
        self._mesh_plan_error = None
        self._mesh_lock = threading.Lock()
        # Only one shard_map program may be in flight on the device
        # set at a time: the entry's cross-shard psum rendezvous
        # deadlocks if a second program (e.g. an unbatched prober
        # probe racing the batcher worker) interleaves its collectives
        # on the same devices.
        self._mesh_exec_lock = threading.Lock()
        self._chunked_db = None
        self._chunked_db_lock = threading.Lock()
        # Runtime tier demotion: num_keys -> minimum tier index in
        # _TIERS after a device OOM proved the budget model optimistic
        # for that batch shape.
        self._tier_floor: dict[int, int] = {}
        self._log_domain_size = max(
            0, math.ceil(math.log2(database.size))
        )
        self._dpf = DistributedPointFunction.create(
            DpfParameters(
                log_domain_size=self._log_domain_size,
                value_type=XorType(128),
            )
        )
        # Only the first ceil(size/128) leaf blocks carry selection bits;
        # expand just the covering subtree (see dense_eval.py).
        self._num_blocks = database.num_selection_blocks
        k = max(0, (self._num_blocks - 1).bit_length())
        # Branching levels = number of correction words (the root level in
        # `_tree_levels_needed` does not branch).
        total_levels = self._dpf._tree_levels_needed - 1
        self._expand_levels = min(k, total_levels)
        self._walk_levels = total_levels - self._expand_levels
        # Build/load the native oracle for the host zeros-walk here, not
        # on the first request (a cold checkout spawns the g++ build).
        # Warm whenever a walk exists, regardless of the current
        # DPF_TPU_HOST_WALK value: handle_request re-reads the env per
        # request, so the flag may be flipped on after construction and
        # the first live request must not pay the g++ build.
        from .dense_eval import warm_host_walk

        if self._walk_levels > 0:
            warm_host_walk()

    # -- constructors mirroring CreatePlain/Leader/Helper -------------------

    @classmethod
    def create_plain(
        cls, database: DenseDpfPirDatabase, mesh=None
    ) -> "DenseDpfPirServer":
        return cls(database, mesh=mesh)

    @classmethod
    def create_leader(
        cls,
        database: DenseDpfPirDatabase,
        sender: ForwardHelperRequestFn,
        mesh=None,
    ) -> "DenseDpfPirServer":
        server = cls(database, mesh=mesh)
        server.make_leader(sender)
        return server

    @classmethod
    def create_helper(
        cls,
        database: DenseDpfPirDatabase,
        decrypter: DecryptHelperRequestFn,
        mesh=None,
    ) -> "DenseDpfPirServer":
        server = cls(database, mesh=mesh)
        server.make_helper(decrypter, ENCRYPTION_CONTEXT_INFO)
        return server

    @property
    def dpf(self) -> DistributedPointFunction:
        return self._dpf

    @property
    def database(self) -> DenseDpfPirDatabase:
        return self._database

    def swap_database(
        self, database: DenseDpfPirDatabase
    ) -> DenseDpfPirDatabase:
        """Atomically replace the served database with a new generation.

        Only safe at a batch boundary: `handle_plain_request` reads
        `self._database` several times per call, so the caller
        (`serving/snapshots.py`) must guarantee no evaluation is in
        flight. Geometry must match — the DPF parameters, expand/walk
        split, and sharded/chunked plans are all derived from the
        original database and are kept; same-geometry replacements only
        need the staged-buffer caches dropped.

        Returns the old database (still staged; the caller drains and
        frees it via `release_stagings()`).
        """
        if database is None:
            raise ValueError("database cannot be None")
        old = self._database
        if database.size != old.size:
            raise ValueError(
                f"swap_database size mismatch: {database.size} != {old.size}"
            )
        if database.num_selection_blocks != old.num_selection_blocks:
            raise ValueError(
                "swap_database selection-block mismatch: "
                f"{database.num_selection_blocks} != "
                f"{old.num_selection_blocks}"
            )
        if database.max_value_size != old.max_value_size:
            raise ValueError(
                "swap_database max_value_size mismatch: "
                f"{database.max_value_size} != {old.max_value_size}"
            )
        self._database = database
        with self._chunked_db_lock:
            self._chunked_db = None
        # The sharded step (a compiled function of the geometry) is
        # reusable; only the placed database must restage.
        self._sharded_db = None
        if self._sharded_step is not None:
            from ..parallel.sharded import pad_rows_to_mesh, shard_database

            ndev = self._mesh.devices.size
            self._sharded_db = shard_database(
                self._mesh, pad_rows_to_mesh(database.db_words, ndev)
            )
        with self._mesh_lock:
            plan = self._mesh_plan
        if plan is not None:
            # All shards flip in this one reference assignment: the new
            # generation's sharded staging is assembled in full (a cache
            # hit when `prestage_database` ran during snapshot staging)
            # before any request can observe it, so no request ever sees
            # shard i from generation N and shard j from N+1.
            self._mesh_db = database.streaming_chunks(
                cut_levels=plan.cut_levels,
                bitmajor=plan.bitmajor,
                mesh=self._mesh,
                shard_axis=plan.shard_axis,
            )
        return old

    def _parse_helper_request(self, data: bytes) -> "messages.HelperRequest":
        return messages.parse_helper_request(self._dpf, data)

    def handle_plain_request(
        self, request: "messages.PirRequest"
    ) -> "messages.PirResponse":
        if request.plain_request is None:
            raise ValueError("request must contain a valid PlainRequest")
        keys = request.plain_request.dpf_keys
        if not keys:
            raise ValueError("dpf_keys must not be empty")
        expected_cw = self._dpf._tree_levels_needed - 1
        for key in keys:
            if key.party not in (0, 1):
                raise ValueError("key.party must be 0 or 1")
            if len(key.correction_words) != expected_cw:
                raise ValueError(
                    f"key has {len(key.correction_words)} correction words, "
                    f"expected {expected_cw}"
                )
        impl, bitrev = serving_expansion()
        if impl is evaluate_selection_blocks and donation_enabled():
            # ROADMAP 3c: the materialized single-device entry donates
            # its per-request staged key tensors (freshly placed by
            # `stage_keys_walked`, dead after the call) so XLA can
            # reuse their HBM for the selection matrix. The resident
            # database buffer is a different argument path entirely
            # (`inner_product_with`) and is never donated.
            impl = evaluate_selection_blocks_donated
        if bitrev and (1 << self._expand_levels) < self._num_blocks:
            # The tree cannot cover the padded block count (domain
            # smaller than the database): the bitrev staging has no
            # zero-extension story there, so serve natural order.
            bitrev = False
        telemetry = default_telemetry()
        # Phase attribution: the first dispatch of `pir.plain` at a new
        # shape is dominated by trace+compile ("compile"); re-dispatches
        # are the steady-state device step ("device_compute"). `seen` is
        # checked BEFORE entering dispatch() — dispatch registers the
        # shape on exit.
        seen = telemetry.compile_tracker.seen
        if self._mesh_is_2d():
            inner_products = self._serve_mesh(keys, telemetry, seen)
            if inner_products is None:  # mesh demoted by a device OOM
                inner_products = self._serve_single_device(
                    keys, bitrev, impl, telemetry, seen
                )
        elif self._mesh is not None:
            with phases_mod.phase("h2d_transfer"):
                staged = stage_keys(keys)
            key = shape_key(
                ("m", "sharded"), ("q", len(keys)), ("b", self._num_blocks)
            )
            step = "device_compute" if seen("pir.plain", key) else "compile"
            with telemetry.hbm.phase("selection"), \
                    telemetry.compile_tracker.dispatch(
                        "pir.plain", key) as dispatch, \
                    phases_mod.phase(
                        step, span="evaluate_sharded", num_keys=len(keys)
                    ) as timing:
                dispatch.timing = timing
                inner_products = self._inner_products_sharded(
                    staged, len(keys)
                )
        else:
            inner_products = self._serve_single_device(
                keys, bitrev, impl, telemetry, seen
            )
        return messages.PirResponse(
            dpf_pir_response=messages.DpfPirResponse(
                masked_response=inner_products
            )
        )

    # -- single-device serving with runtime tier demotion --------------------

    # Planner tiers ordered by decreasing peak HBM appetite; a device
    # OOM at dispatch demotes the shape to the next tier and retries.
    _TIERS = ("materialized", "streaming", "chunked")

    def _serve_single_device(self, keys, bitrev, impl, telemetry, seen):
        """Plan and execute one single-device batch, retrying at the
        next planner tier down when the device reports OOM at dispatch
        (the budget model is an estimate; the device is the truth)."""
        while True:
            plan = self._plan_serving(len(keys), bitrev)
            try:
                return self._execute_plan(
                    plan, keys, bitrev, impl, telemetry, seen
                )
            except Exception as exc:  # noqa: BLE001 - OOM-gated below
                if not self._demote_tier_on_oom(plan, len(keys), exc):
                    raise

    def _execute_plan(self, plan, keys, bitrev, impl, telemetry, seen):
        # Stamp the executed planner tier onto the enclosing phase
        # record (the batcher's batch-scoped record during batched
        # serving): the cost-ledger join reads `serving_plan` back to
        # key its predicted-vs-actual residual cell by tier.
        record = phases_mod.current_request()
        if record is not None:
            record.set_meta(
                "serving_plan",
                {"mode": plan.mode, "num_keys": plan.num_keys},
            )
        if plan.mode == "streaming":
            key = shape_key(
                ("m", f"streaming-{plan.ip}"),
                ("q", plan.num_keys),
                ("b", self._num_blocks),
                ("c", plan.cut_levels),
            )
            step = (
                "device_compute" if seen("pir.plain", key) else "compile"
            )
            with telemetry.hbm.phase("selection"), \
                    telemetry.compile_tracker.dispatch(
                        "pir.plain", key) as dispatch, \
                    phases_mod.phase(
                        step, span="evaluate_streaming",
                        num_keys=plan.num_keys, ip=plan.ip,
                    ) as timing:
                dispatch.timing = timing
                return self._inner_products_streaming(plan, keys)
        if plan.mode == "chunked":
            with phases_mod.phase("h2d_transfer"):
                staged = stage_keys(keys)
            key = shape_key(
                ("m", "chunked"),
                ("q", plan.num_keys),
                ("b", self._num_blocks),
                ("c", plan.chunk_levels),
            )
            step = (
                "device_compute" if seen("pir.plain", key) else "compile"
            )
            with telemetry.hbm.phase("selection"), \
                    telemetry.compile_tracker.dispatch(
                        "pir.plain", key) as dispatch, \
                    phases_mod.phase(
                        step, span="evaluate_chunked", num_keys=plan.num_keys
                    ) as timing:
                dispatch.timing = timing
                return self._inner_products_chunked(
                    staged, plan.num_keys, plan
                )
        # Walk the shared all-zeros prefix on the host during staging
        # (sub-ms there vs ~1.4 ms of dispatch-bound device AES per
        # batch); the device step starts at the expansion root.
        # DPF_TPU_HOST_WALK=0 restores the on-device walk.
        key = shape_key(
            ("m", "bitrev" if bitrev else "materialized"),
            ("q", plan.num_keys),
            ("b", self._num_blocks),
        )
        step = "device_compute" if seen("pir.plain", key) else "compile"
        with telemetry.hbm.phase("selection"), \
                telemetry.compile_tracker.dispatch(
                    "pir.plain", key) as dispatch, \
                phases_mod.phase(
                    step, span="evaluate_materialized", num_keys=plan.num_keys
                ) as timing:
            dispatch.timing = timing
            # Nested bracket: staging time lands in h2d_transfer
            # and is deducted from the enclosing compute phase
            # (exclusive-time semantics).
            with phases_mod.phase("h2d_transfer"):
                staged, device_walk = stage_keys_walked(
                    keys, self._walk_levels
                )
            selections = impl(
                *staged,
                walk_levels=device_walk,
                expand_levels=self._expand_levels,
                num_blocks=self._num_blocks,
                **({"bitrev_leaves": True} if bitrev else {}),
            )
            return self._database.inner_product_with(
                selections, bitrev_blocks=bitrev
            )

    @staticmethod
    def _is_resource_exhausted(exc: BaseException) -> bool:
        text = f"{type(exc).__name__}: {exc}"
        return any(
            marker in text
            for marker in ("RESOURCE_EXHAUSTED", "Out of memory", "OOM")
        )

    def _demote_tier_on_oom(
        self, plan: ServingPlan, num_keys: int, exc: BaseException
    ) -> bool:
        """Record a device OOM against `num_keys` and say whether the
        batch can retry one tier down. Non-OOM errors never demote."""
        if not self._is_resource_exhausted(exc):
            return False
        current = self._TIERS.index(plan.mode)
        if current + 1 >= len(self._TIERS) or self._expand_levels <= 0:
            return False  # already at the floor tier; nothing below
        floor = max(self._tier_floor.get(num_keys, 0), current + 1)
        self._tier_floor[num_keys] = floor
        demoted = self._TIERS[floor]
        tracing.runtime_counters.inc("pir.tier_demotions")
        tracing.runtime_counters.inc(
            f"pir.tier_demote.{plan.mode}_to_{demoted}"
        )
        events_mod.emit(
            "pir.tier_demotion",
            f"{num_keys} keys: {plan.mode} -> {demoted} after device OOM",
            severity="warning",
            num_keys=num_keys,
            from_tier=plan.mode,
            to_tier=demoted,
        )
        import warnings

        warnings.warn(
            f"device OOM serving {num_keys} keys in {plan.mode} mode; "
            f"demoting this shape to the {demoted} tier "
            f"({str(exc).splitlines()[0][:200]})"
        )
        return True

    # -- over-budget serving (selection tensor larger than the HBM budget) ---

    def _plan_serving(self, num_keys: int, bitrev: bool) -> ServingPlan:
        """One planner call decides materialized / streaming / chunked
        and the streaming cut/chunk split (see `planner.py` for the HBM
        budget model). A remembered device OOM for this batch shape
        pins the planner at a lower tier."""
        import jax

        plan = plan_dense_serving(
            num_keys=num_keys,
            num_blocks=self._num_blocks,
            expand_levels=self._expand_levels,
            serving_bitrev=bitrev,
            backend=jax.default_backend(),
        )
        floor = max(self._tier_floor.get(num_keys, 0), _GLOBAL_TIER_FLOOR)
        if floor and self._TIERS.index(plan.mode) < floor:
            plan = plan_dense_serving(
                num_keys=num_keys,
                num_blocks=self._num_blocks,
                expand_levels=self._expand_levels,
                serving_bitrev=bitrev,
                backend=jax.default_backend(),
                force_mode=self._TIERS[floor],
            )
        return plan

    def _selection_budget_bytes(self) -> int:
        return selection_budget_bytes()

    def _needs_chunking(self, num_keys: int, blocks: int = None) -> bool:
        """Whether a batch of `num_keys` exceeds the materialized HBM
        budget (planner-backed shim; the planner also picks WHICH
        over-budget mode serves it)."""
        if blocks is None:
            blocks = self._num_blocks
        return (
            num_keys * blocks * 16 > self._selection_budget_bytes()
            and self._expand_levels > 0
        )

    def _inner_products_streaming(self, plan: ServingPlan, keys):
        """Serve via the fused streaming scan: tail expansion and XOR
        inner product per chunk, no materialized selection matrix
        (`dense_eval_planes_v2.streaming_pir_inner_products_v2`)."""
        import numpy as np

        from .dense_eval_planes_v2 import streaming_pir_inner_products_v2

        num_keys = len(keys)
        with phases_mod.phase("h2d_transfer"):
            staged, device_walk = stage_keys_walked(keys, self._walk_levels)

        db_chunks = self._database.streaming_chunks(
            cut_levels=plan.cut_levels, bitmajor=(plan.ip == "pallas2")
        )
        out = np.asarray(
            streaming_pir_inner_products_v2(
                *staged,
                db_chunks,
                walk_levels=device_walk,
                cut_levels=plan.cut_levels,
                chunk_levels=plan.chunk_levels,
                ip=plan.ip,
            )
        )
        return words_to_record_bytes(
            out, num_keys, self._database.max_value_size
        )

    # Chunk-granule cap: the chunked database is padded to a multiple of
    # 2^_CHUNK_GRANULE_LEVELS blocks once, so the padded buffer (and with
    # it the scan's chunk arithmetic) is independent of the request's
    # batch size — alternating batch sizes must not re-pad the database.
    _CHUNK_GRANULE_LEVELS = 10  # 1024 blocks = 2^17 records per granule

    def _chunked_database(self):
        """The padded chunked-db buffer (built once, under a lock —
        handle_plain_request supports concurrent callers)."""
        with self._chunked_db_lock:
            if self._chunked_db is None:
                import jax.numpy as jnp

                granule = 1 << min(
                    self._expand_levels, self._CHUNK_GRANULE_LEVELS
                )
                padded_blocks = -(-self._num_blocks // granule) * granule
                db = self._database.db_words
                pad = padded_blocks * 128 - db.shape[0]
                if pad > 0:
                    db = jnp.concatenate(
                        [db, jnp.zeros((pad, db.shape[1]), db.dtype)]
                    )
                self._chunked_db = (padded_blocks, db)
        return self._chunked_db

    def _inner_products_chunked(
        self, staged, num_keys: int, plan: ServingPlan
    ):
        """Serve via the legacy `chunked_pir_inner_products` loop: only
        one chunk's selection blocks are ever live. Kept for geometries
        the streaming scan cannot serve (trees that do not cover the
        padded block count) and for `DPF_TPU_STREAMING=0`.

        The budget bounds the live *packed* leaf tensor
        (nq * chunk_blocks * 16 bytes); the inner product itself runs
        through the row-chunked kernel, so its intermediates are bounded
        independently of chunk size.
        """
        import numpy as np

        from .dense_eval import (
            chunked_pir_inner_products,
            chunked_pir_inner_products_donated,
        )

        kernel = (
            chunked_pir_inner_products_donated
            if donation_enabled()
            else chunked_pir_inner_products
        )
        padded_blocks, db = self._chunked_database()
        # The planner caps chunk_expand_levels by budget and granule;
        # the chunk count re-derives from the granule-padded block
        # count (plan.num_chunks is the unpadded lower bound).
        cel = min(plan.chunk_levels, self._CHUNK_GRANULE_LEVELS)
        chunk_bits = self._expand_levels - cel
        num_chunks = padded_blocks >> cel

        out = np.asarray(
            kernel(
                *staged,
                db,
                walk_levels=self._walk_levels,
                chunk_bits=chunk_bits,
                chunk_expand_levels=cel,
                num_chunks=num_chunks,
            )
        )
        return words_to_record_bytes(
            out, num_keys, self._database.max_value_size
        )

    # -- mesh serving (2-D shard x key mesh) ----------------------------------

    def _mesh_is_2d(self) -> bool:
        return (
            self._mesh is not None
            and len(getattr(self._mesh, "axis_names", ())) == 2
        )

    def batch_key_multiple(self) -> int:
        """Key-batch granularity the serving runtime should pad buckets
        to: the key-axis size on a 2-D mesh (so batches land
        pre-partitioned without a gather), 1 otherwise."""
        if not self._mesh_is_2d():
            return 1
        return int(self._mesh.shape[tuple(self._mesh.axis_names)[1]])

    def _ensure_mesh_plan(self, num_keys_hint: int):
        """Build (once) the 2-D serving plan and the mesh-sharded
        database staging. A plan that cannot be built raises: a mesh
        the caller asked for is never silently replaced by one device.
        Returns None only after a device OOM demoted the mesh (counted
        in `pir.mesh_fallbacks`)."""
        with self._mesh_lock:
            if self._mesh_plan is not None:
                return self._mesh_plan
            if self._mesh_plan_error is not None:
                return None
            plan = self._build_mesh_plan(num_keys_hint)
            db = self._database.streaming_chunks(
                cut_levels=plan.cut_levels,
                bitmajor=plan.bitmajor,
                mesh=self._mesh,
                shard_axis=plan.shard_axis,
            )
            self._mesh_plan = plan
            self._mesh_db = db
            return plan

    def _build_mesh_plan(self, num_keys_hint: int):
        import jax

        from ..capacity.model import default_capacity_model
        from ..parallel.sharded import ShardedServingPlan

        axis_names = tuple(self._mesh.axis_names)
        shards = int(self._mesh.shape[axis_names[0]])
        key_devices = int(self._mesh.shape[axis_names[1]])
        s_levels = max(0, (shards - 1).bit_length())
        if (1 << s_levels) != shards:
            raise ValueError(
                f"shard axis must be a power of two, got {shards}"
            )
        # The streaming staging pads rows to the full covering subtree
        # (2^expand blocks), so the scan geometry must cover it exactly.
        expand = max(0, (self._num_blocks - 1).bit_length())
        total_levels = self._dpf._tree_levels_needed - 1
        if expand > total_levels:
            raise ValueError(
                f"tree depth {total_levels} cannot cover 2^{expand} "
                "padded blocks"
            )
        if expand < s_levels:
            raise ValueError(
                f"2^{expand} chunk lanes cannot split over {shards} "
                "shards"
            )
        model = default_capacity_model()
        local_keys = -(-max(1, num_keys_hint) // key_devices)
        chunk = min(
            model.pick_streaming_split(local_keys, expand),
            expand - s_levels,
        )
        cut = expand - chunk
        return ShardedServingPlan(
            self._mesh,
            walk_levels=total_levels - expand,
            cut_levels=cut,
            chunk_levels=chunk,
            ip=streaming_ip(jax.default_backend()),
        )

    def _note_mesh_oom(self, exc: BaseException) -> None:
        import warnings

        tracing.runtime_counters.inc("pir.mesh_fallbacks")
        events_mod.emit(
            "pir.mesh_fallback",
            "mesh serving disabled after a device OOM; serving "
            "single-device",
            severity="warning",
            stage="dispatch",
            error=str(exc).splitlines()[0][:200],
        )
        warnings.warn(
            "mesh serving hit a device OOM; serving single-device "
            f"({str(exc).splitlines()[0][:200]})"
        )

    def _serve_mesh(self, keys, telemetry, seen):
        """One batch through the 2-D mesh plan. Returns the response
        list, or None to serve single-device after a device OOM that
        permanently demoted the mesh."""
        plan = self._ensure_mesh_plan(len(keys))
        if plan is None:
            return None
        record = phases_mod.current_request()
        if record is not None:
            record.set_meta(
                "serving_plan", {"mode": "mesh", "num_keys": len(keys)}
            )
        # Host-side assembly only: the placement is the plan's sharded
        # stage_keys, so keys go straight to their key-axis devices
        # pre-partitioned (no single-device detour, no dispatch-time
        # relayout).
        with self._mesh_exec_lock:
            with phases_mod.phase("h2d_transfer"):
                staged_host = stage_keys_host(keys)
                staged = plan.stage_keys(staged_host)
            key = shape_key(
                ("m", f"mesh-{plan.ip}"),
                ("q", int(staged[0].shape[0])),
                ("b", self._num_blocks),
                ("c", plan.cut_levels),
            )
            step = (
                "device_compute" if seen("pir.plain", key) else "compile"
            )
            mesh_db = self._mesh_db
            try:
                with telemetry.hbm.phase("selection"), \
                        telemetry.compile_tracker.dispatch(
                            "pir.plain", key
                        ) as dispatch, \
                        phases_mod.phase(
                            step, span="evaluate_mesh",
                            num_keys=len(keys),
                            shards=plan.num_shards,
                            key_devices=plan.num_key_devices,
                        ) as timing:
                    dispatch.timing = timing
                    with tracing.span("pir.inner_product", record=False):
                        out_dev = plan.run(staged, mesh_db)
                    with tracing.span("pir.readback", record=False):
                        out = telemetry.transfers.to_host(
                            out_dev, phase="result_readback"
                        )
            except Exception as exc:  # noqa: BLE001 - OOM-gated below
                if not self._is_resource_exhausted(exc):
                    raise
                with self._mesh_lock:
                    self._mesh_plan = None
                    self._mesh_db = None
                    self._mesh_plan_error = exc
                self._note_mesh_oom(exc)
                return None
        return words_to_record_bytes(
            out, len(keys), self._database.max_value_size
        )

    def prestage_database(self, database: DenseDpfPirDatabase) -> int:
        """Stage `database` exactly the way THIS server will serve it
        (snapshots call this for generation N+1 so the flip is a cache
        hit): the mesh-sharded streaming staging when a 2-D plan is
        active, the row-major single-device buffer otherwise. Returns
        bytes staged."""
        if self._mesh_is_2d():
            plan = self._ensure_mesh_plan(num_keys_hint=64)
            if plan is not None:
                return database.prestage(
                    mesh=self._mesh,
                    cut_levels=plan.cut_levels,
                    bitmajor=plan.bitmajor,
                    shard_axis=plan.shard_axis,
                )
        return database.prestage()

    def mesh_export(self) -> dict:
        """The /statusz "Mesh" view: mesh shape, plan geometry, scratch
        pool and donation state, per-shard staging detail, per-shard
        HBM watermarks."""
        if self._mesh is None:
            return {"configured": False}
        axis_names = tuple(getattr(self._mesh, "axis_names", ()))
        out = {
            "configured": True,
            "axis_names": list(axis_names),
            "shape": {
                str(name): int(self._mesh.shape[name])
                for name in axis_names
            },
            "devices": int(self._mesh.devices.size),
            "two_dee": len(axis_names) == 2,
        }
        with self._mesh_lock:
            plan = self._mesh_plan
            err = self._mesh_plan_error
        if err is not None:
            out["fallback_error"] = str(err).splitlines()[0][:200]
        if plan is not None:
            out["plan"] = plan.export()
        info = self._database.mesh_staging_info()
        if info is not None:
            watermarks = default_telemetry().hbm.export().get(
                "watermark_bytes", {}
            )
            for shard in info.get("shards", ()):
                shard["hbm_watermark_bytes"] = watermarks.get(
                    f"db_staging/dev{shard['device']}"
                )
            out["staging"] = info
        return out

    # -- multi-chip serving ---------------------------------------------------

    def _ensure_sharded(self):
        """Build the sharded step and place the record-sharded database
        (once): rows pad to 128 * mesh size, and the expansion produces the
        padded block count so every device's bit range is covered."""
        if self._sharded_step is not None:
            return
        from ..parallel.sharded import (
            pad_rows_to_mesh,
            shard_database,
            sharded_dense_pir_step,
        )

        ndev = self._mesh.devices.size
        db = pad_rows_to_mesh(self._database.db_words, ndev)
        num_blocks = db.shape[0] // 128
        total_levels = self._dpf._tree_levels_needed - 1
        expand_levels = min(
            max(0, (num_blocks - 1).bit_length()), total_levels
        )
        self._sharded_step = sharded_dense_pir_step(
            self._mesh,
            walk_levels=total_levels - expand_levels,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
            real_num_blocks=self._database.num_selection_blocks,
        )
        self._sharded_db = shard_database(self._mesh, db)

    def _inner_products_sharded(self, staged, num_keys: int):
        import numpy as np

        from ..parallel.sharded import pad_staged_queries

        self._ensure_sharded()
        staged = pad_staged_queries(staged, self._mesh.devices.size)
        out = np.asarray(self._sharded_step(*staged, self._sharded_db))
        return words_to_record_bytes(
            out, num_keys, self._database.max_value_size
        )
