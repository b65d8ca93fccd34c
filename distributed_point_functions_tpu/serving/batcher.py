"""Thread-safe dynamic batcher: coalesce concurrent PIR requests.

The TPU serving cost model (PR 1's planner/streaming pipeline, and
BBCGGI arXiv:2012.14884 before it) is dominated by batched DPF
evaluation: throughput scales with the number of keys a single device
step evaluates, while a one-key step pays the whole dispatch cost.
Nothing in the library formed those batches — every caller of
`handle_plain_request` paid its own device step. This batcher is the
missing piece:

* Concurrent `submit(keys)` calls coalesce into one evaluation of their
  concatenated keys. A batch closes when it holds `max_batch_size` keys
  or `max_wait_ms` after its first request arrived, whichever is first.
* The batch's key count pads up to a **power-of-two bucket** (duplicate
  of the first key; its result is discarded). Every jitted program in
  the serving path specializes on `num_keys`, so bucketing bounds the
  number of distinct compilations at `log2(max_batch_size)+1` instead
  of one per observed arrival pattern — each bucket hits an existing
  jit/planner cache entry.
* Admission is a **bounded queue**: when `max_queue` requests are
  already waiting, `submit` sheds load with `Overloaded` instead of
  growing an unbounded backlog. With an `AdmissionController`
  (`capacity/admission.py`) attached, the count bound becomes a
  backstop behind cost-aware admission: each request is priced in
  estimated device-ms, doomed work (drain estimate past the deadline)
  and over-quota tenants are shed *at admission* with a
  `retry_after_s` hint on the `Overloaded`, and the queue dequeues
  across tenants in weighted-fair order instead of global FIFO.
* Requests carry an optional absolute **deadline** (`time.monotonic()`
  seconds; the batcher's own stamps of submit, batch gate and
  evaluation are on the span log's clock, `time.perf_counter_ns()`).
  The worker drops requests that expired while the batch was
  forming *before* any device work is dispatched — they fail with
  `DeadlineExceeded` without evaluating, and a bucket whose every
  request died skips the dispatch entirely — and the submitting thread
  enforces the same deadline on its wait.
* With `pipeline_depth >= 2` the worker runs a bounded two-stage
  pipeline: it dispatches bucket N (admission gate, padding,
  `begin_batch` generation binding, the evaluation itself) while a
  completion thread finishes bucket N-1 (result fan-out, phase
  attribution, `end_batch`, cost-ledger feed). The handoff queue holds
  at most `pipeline_depth - 1` evaluated buckets, so the worker never
  runs further ahead than the pipeline depth. Semantics are preserved
  exactly: the generation is still bound at dispatch by the worker
  (serial, so flips still land only at batch boundaries), a bucket's
  `end_batch` still fires only after its last response fans out (so a
  rotation can never free buffers or idle-flip between the dispatch
  and completion halves), and `close()` drains the in-flight stage
  before returning. Depth 1 completes inline — the pre-pipeline
  behavior, bit for bit.

Spans: every request logs `batcher.queue_wait` (submit to the batch
gate, out of band, with the party) and every batch runs inside one
`batcher.evaluate` span (bucket, live keys, party) whose timing also
feeds the `<name>.evaluate_ms` histogram, the compile tracker and the
phase split: one timing for all of them.

The batcher is generic over the evaluation function
(`evaluate(keys) -> list of per-key results`), so it serves any of the
server roles (and unit tests run it on stubs with no JAX at all).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence

from ..capacity.admission import AdmissionController, WeightedFairQueue
from ..capacity.model import default_capacity_model
from ..observability import costmodel as costmodel_mod
from ..observability import tracing
from ..observability import phases as phases_mod
from ..observability.device import default_telemetry, shape_key
from ..robustness import failpoints
from .metrics import MetricsRegistry


class Overloaded(RuntimeError):
    """The request was shed at admission, not enqueued. `retry_after_s`
    is the server's drain-based backoff hint (0 = none given) and
    `reason` the admission `ShedReason` value string, when cost-aware
    admission made the call."""

    def __init__(
        self,
        message: str = "",
        retry_after_s: float = 0.0,
        reason: Optional[str] = None,
    ):
        super().__init__(message)
        self.retry_after_s = retry_after_s
        self.reason = reason


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before its batch was evaluated."""


def bucket_size(num_keys: int) -> int:
    """Smallest power of two >= num_keys (the jit-shape bucket)."""
    if num_keys <= 0:
        raise ValueError("num_keys must be positive")
    return 1 << (num_keys - 1).bit_length()


def _h2d_bytes(telemetry) -> int:
    """Cumulative host->device bytes from the transfer ledger (0 when
    unavailable); deltas across a batch attribute its staging traffic
    to the cost ledger."""
    try:
        return int(telemetry.transfers.export()["totals"]["h2d_bytes"])
    except Exception:  # noqa: BLE001 - accounting never breaks serving
        return 0


def _sync_wait_ms(telemetry) -> float:
    """Cumulative exposed transfer-sync wall time from the ledger (0.0
    when unavailable); deltas across an evaluation split the wall time
    into device-feeding work vs `staging_sync` bubbles."""
    try:
        return float(telemetry.transfers.sync_wait_ms())
    except Exception:  # noqa: BLE001 - accounting never breaks serving
        return 0.0


class _Pending:
    __slots__ = (
        "keys", "deadline", "event", "result", "error", "t0_ns",
        "abandoned", "trace", "parent", "phases", "tenant", "cost",
        "generation",
    )

    def __init__(self, keys, deadline, tenant="default", cost=None):
        self.keys = keys
        self.deadline = deadline
        self.tenant = tenant
        self.cost = cost  # admission WorkCost, released on completion
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t0_ns = time.perf_counter_ns()
        self.abandoned = False
        # Snapshot generation the batch actually evaluated against
        # (None without a generation source); stamped by the worker at
        # the batch boundary, read back through `submit_ex`.
        self.generation: Optional[int] = None
        # The submitting request's trace: the worker thread appends the
        # queue-wait / device-compute spans onto it by reference. Same
        # deal for the phase record — the worker attributes
        # queue/batch/compile/device phases onto it.
        self.trace = tracing.current_trace()
        # The submitter's innermost logged span: the queue wait's parent.
        self.parent = tracing.current_span()
        self.phases = phases_mod.current_request()


class _BatchResult:
    """One evaluated bucket between the worker's dispatch half and the
    completion half. Everything the fan-out needs is captured at
    dispatch time (on the worker), so completion never reads worker
    state that a later bucket may have advanced."""

    __slots__ = (
        "live", "results", "error", "collected", "eval_ms", "assembly_s",
        "bucket", "flat_len", "pad_waste", "generation", "batch_phases",
        "transfer_bytes", "gate_ns", "eval_ns",
    )

    def __init__(self):
        self.results = None
        self.error = None
        self.collected = {}
        self.eval_ms = 0.0
        self.batch_phases = None
        self.transfer_bytes = 0


class DynamicBatcher:
    """See module docstring. One background worker forms and evaluates
    batches (plus, at `pipeline_depth >= 2`, a completion thread that
    fans out bucket N-1 while the worker dispatches bucket N); `submit`
    blocks the calling thread until its slice of the batch result is
    ready (or raises `Overloaded` / `DeadlineExceeded` / the
    evaluation error)."""

    def __init__(
        self,
        evaluate: Callable[[List], List],
        *,
        max_batch_size: int = 64,
        max_wait_ms: float = 2.0,
        max_queue: int = 256,
        metrics: Optional[MetricsRegistry] = None,
        name: str = "batcher",
        admission: Optional[AdmissionController] = None,
        pipeline_depth: int = 1,
    ):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self._evaluate = evaluate
        self._max_batch_size = max_batch_size
        self._batch_cap: Optional[int] = None  # brownout step 2
        self._max_wait_s = max(0.0, max_wait_ms) / 1e3
        self._max_queue = max_queue
        self._admission = admission
        self._name = name
        # The serving role this batcher belongs to (the span attr).
        self._party = name[: -len(".batcher")] if name.endswith(
            ".batcher") else name
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m, n = self.metrics, name
        self._c_submitted = m.counter(f"{n}.requests_submitted")
        self._c_shed = m.counter(f"{n}.requests_shed")
        self._c_deadline = m.counter(f"{n}.requests_deadline_exceeded")
        self._c_batches = m.counter(f"{n}.batches")
        self._c_pad = m.counter(f"{n}.padded_keys")
        self._c_compiles = m.counter(f"{n}.jit_bucket_compiles")
        self._c_hits = m.counter(f"{n}.jit_bucket_hits")
        self._g_depth = m.gauge(f"{n}.queue_depth")
        self._h_batch = m.histogram(
            f"{n}.batch_keys", buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256)
        )
        self._h_latency = m.histogram(f"{n}.request_latency_ms")
        self._h_evaluate = m.histogram(f"{n}.evaluate_ms")
        self._h_queue_wait = m.histogram(f"{n}.queue_wait_ms")
        self._h_pad_waste = m.histogram(
            f"{n}.pad_waste_ratio",
            buckets=(0.0, 0.05, 0.1, 0.25, 0.5, 0.75, 0.875, 1.0),
        )
        self._c_expired_in_batch = m.counter(f"{n}.expired_in_batch")
        self._c_batches_skipped = m.counter(f"{n}.batches_skipped_dead")
        # Phase-attribution residual that would have gone negative:
        # collected phase brackets exceeded the measured wall time
        # (clock skew, nested brackets). `dispatch` clamps at zero and
        # the excess lands here instead of corrupting the residual.
        self._c_slop = m.counter(f"{n}.attribution_slop_ms")
        m.gauge(f"{n}.pipeline_depth").set(float(pipeline_depth))
        self._cond = threading.Condition()
        # Weighted-fair across tenants under cost-aware admission;
        # plain FIFO otherwise (and WFQ degenerates to FIFO for a
        # single tenant, so either way one-tenant order is arrival
        # order).
        self._queue = WeightedFairQueue() if admission is not None else deque()
        # Snapshot rotation hook (`serving/snapshots.py`): the worker
        # calls begin_batch()/end_batch() around every evaluation so
        # flips land only at batch boundaries and in-flight batches
        # pin their generation's stagings.
        self._generation_source = None
        # Utilization hook (`observability/utilization.py`): the worker
        # and completion threads report busy/idle intervals with typed
        # bubble causes (see `set_utilization`). None = no accounting.
        self._util = None
        # Key-bucket granularity: mesh serving pads buckets to a
        # multiple of the key-axis size so batches land pre-partitioned
        # over the key axis (see `set_key_multiple`). 1 = plain
        # power-of-two buckets.
        self._key_multiple = 1
        # Cost-ledger identity: which workload the terminal batches are
        # joined under and how a bucket is priced (see
        # `set_cost_model`). Defaults to dense pir pricing.
        self._cost_workload = "pir"
        self._cost_pricer = None
        self._seen_buckets: set = set()
        self._closed = False
        # Depth-2 pipeline handoff: the worker appends evaluated
        # buckets, the completion thread pops them. Bounded at
        # pipeline_depth - 1 so the worker can run at most one bucket
        # ahead of the completion half (depth 1 => no thread, inline
        # completion = pre-pipeline behavior).
        self._pipeline_depth = int(pipeline_depth)
        self._complete_q: deque = deque()
        self._complete_cond = threading.Condition()
        self._worker_done = False
        self._completer: Optional[threading.Thread] = None
        if self._pipeline_depth > 1:
            self._completer = threading.Thread(
                target=self._complete_loop, daemon=True,
                name=f"{name}-completer",
            )
            self._completer.start()
        self._worker = threading.Thread(
            target=self._run, daemon=True, name=f"{name}-worker"
        )
        self._worker.start()

    # -- client side --------------------------------------------------------

    def submit(
        self,
        keys: Sequence,
        deadline: Optional[float] = None,
        tenant: str = "default",
    ) -> List:
        """Evaluate `keys` as part of a coalesced batch; returns one
        result per key, in order. `deadline` is absolute
        `time.monotonic()` seconds; `tenant` keys the QoS policy when
        cost-aware admission is attached (ignored otherwise)."""
        results, _ = self.submit_ex(keys, deadline, tenant)
        return results

    def submit_ex(
        self,
        keys: Sequence,
        deadline: Optional[float] = None,
        tenant: str = "default",
    ):
        """`submit`, but returns `(results, generation)` where
        `generation` is the snapshot generation the batch evaluated
        against (None without an attached generation source) — the
        Leader binds its own share to it and refuses a Helper echo
        from any other generation."""
        keys = list(keys)
        if not keys:
            raise ValueError("keys must not be empty")
        # The submitter's wait, from submit to its slice of the results:
        # the batcher's share of the calling request, in the span log.
        with tracing.span("batcher.submit", record=False, party=self._party):
            return self._submit(keys, deadline, tenant)

    def _submit(self, keys, deadline, tenant):
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if len(self._queue) >= self._max_queue:
                # Count bound: the whole admission story without a
                # controller, a backstop behind it (in case the cost
                # model underprices a pathological workload).
                self._c_shed.inc()
                raise Overloaded(
                    f"{self._name}: admission queue full "
                    f"({self._max_queue} requests waiting)"
                )
            cost = None
            if self._admission is not None:
                decision = self._admission.admit(
                    len(keys), tenant=tenant, deadline=deadline
                )
                if not decision.admitted:
                    self._c_shed.inc()
                    raise Overloaded(
                        f"{self._name}: shed at admission "
                        f"({decision.reason.value}); retry after "
                        f"{decision.retry_after_s:.3f}s",
                        retry_after_s=decision.retry_after_s,
                        reason=decision.reason.value,
                    )
                cost = decision.cost
            pending = _Pending(keys, deadline, tenant=tenant, cost=cost)
            if self._admission is not None:
                policy = self._admission.policy(tenant)
                self._queue.push(
                    pending,
                    tenant=tenant,
                    weight=policy.weight,
                    cost=float(len(keys)),
                )
            else:
                self._queue.append(pending)
            self._g_depth.set(len(self._queue))
            self._c_submitted.inc()
            self._cond.notify()
        timeout = (
            None if deadline is None
            else max(0.0, deadline - time.monotonic())
        )
        if not pending.event.wait(timeout):
            with self._cond:
                pending.abandoned = True
            # The worker may complete it concurrently; deadline still wins.
            if not pending.event.is_set() or pending.error is not None:
                self._c_deadline.inc()
                raise DeadlineExceeded(
                    f"{self._name}: deadline passed after "
                    f"{(time.perf_counter_ns() - pending.t0_ns) / 1e6:.1f}"
                    " ms in queue"
                )
        if pending.error is not None:
            raise pending.error
        return pending.result, pending.generation

    # -- snapshot rotation hook ---------------------------------------------

    def set_generation_source(self, source) -> None:
        """Attach a `SnapshotManager` (duck-typed: `begin_batch()`
        returning the bound generation, `end_batch(generation)` when
        the batch retires). Flips then land only between batches, so a
        batch never evaluates half against generation N and half
        against N+1."""
        with self._cond:
            self._generation_source = source

    def _end_batch(self, generation) -> None:
        if generation is None:
            return
        source = self._generation_source
        if source is not None:
            try:
                source.end_batch(generation)
            except Exception:  # noqa: BLE001 - bookkeeping never kills the worker
                pass

    # -- utilization hook ---------------------------------------------------

    def set_utilization(self, tracker) -> None:
        """Attach a `UtilizationTracker` (duck-typed:
        `record_busy(seconds, thread=)` and `record_idle(cause,
        seconds, thread=)`). The worker thread then attributes every
        second it spends to device-feeding work or a typed bubble —
        empty_queue / admission_shed / batch_wait / pipeline_full /
        staging_sync — and the completion thread reports fan-out time;
        None detaches."""
        with self._cond:
            self._util = tracker

    def _util_busy(self, seconds: float, thread: str = "worker") -> None:
        util = self._util
        if util is None or seconds <= 0.0:
            return
        try:
            util.record_busy(seconds, thread=thread)
        except Exception:  # noqa: BLE001 - accounting never breaks serving
            pass

    def _util_idle(
        self, cause: str, seconds: float, thread: str = "worker"
    ) -> None:
        util = self._util
        if util is None or seconds <= 0.0:
            return
        try:
            util.record_idle(cause, seconds, thread=thread)
        except Exception:  # noqa: BLE001 - accounting never breaks serving
            pass

    # -- cost-model hook ----------------------------------------------------

    def set_cost_model(self, workload: str, pricer=None) -> None:
        """Re-key the terminal-batch cost join: `workload` names the
        ledger cell family (dense sessions use "pir", sparse sessions
        "sparse") and `pricer`, when given, maps an executed padded
        bucket size to a `WorkCost` estimate (defaults to the capacity
        model's dense `price_pir_keys`). Sparse serving attaches
        `price_sparse_pir_keys` here so the accuracy ledger and the
        recalibration loop see sparse traffic as its own workload."""
        if not workload:
            raise ValueError("workload must be non-empty")
        with self._cond:
            self._cost_workload = str(workload)
            self._cost_pricer = pricer

    # -- brownout hook ------------------------------------------------------

    def set_batch_cap(self, cap: Optional[int]) -> None:
        """Cap the effective batch size below `max_batch_size` (the
        brownout ladder's `cap_batches` step trades peak throughput
        for shorter queue drains); None clears."""
        if cap is not None and cap < 1:
            raise ValueError("batch cap must be >= 1 (or None)")
        with self._cond:
            self._batch_cap = cap

    # -- mesh hook ----------------------------------------------------------

    def set_key_multiple(self, multiple: int) -> None:
        """Pad every key bucket up to a multiple of `multiple` (the
        serving mesh's key-axis size) so batches flow into the sharded
        step pre-partitioned, with no gather and no fresh jit shape per
        request count. Power-of-two buckets already satisfy any
        power-of-two multiple <= the bucket; the rounding only moves
        buckets smaller than the multiple."""
        if multiple < 1:
            raise ValueError("key multiple must be >= 1")
        with self._cond:
            self._key_multiple = int(multiple)

    # -- worker -------------------------------------------------------------

    def _pop_next(self):
        # Caller holds self._cond.
        return (
            self._queue.pop()
            if self._admission is not None
            else self._queue.popleft()
        )

    def _peek_next(self):
        # Caller holds self._cond.
        if not self._queue:
            return None
        return (
            self._queue.peek()
            if self._admission is not None
            else self._queue[0]
        )

    def _release(self, pending: _Pending) -> None:
        """An admitted request reached a terminal state: give its
        estimated cost back to the admission drain model."""
        if self._admission is not None:
            self._admission.release(pending.cost)

    def _collect(self):
        """Block for the first request, then fill the batch until
        `max_batch_size` keys or `max_wait_ms` elapse. Returns
        (batch, assembly_seconds) — assembly measured from the first
        pop, i.e. the window spent waiting for co-batchable arrivals —
        or None only at shutdown with an empty queue."""
        util = self._util
        empty_s = 0.0
        form_s = 0.0
        shed_before = self._c_shed.value if util is not None else 0
        with self._cond:
            while not self._queue:
                if self._closed:
                    return None
                t_wait = time.monotonic()
                self._cond.wait()
                empty_s += time.monotonic() - t_wait
            t_first = time.monotonic()
            batch = [self._pop_next()]
            num_keys = len(batch[0].keys)
            max_batch = self._max_batch_size
            if self._batch_cap is not None:
                max_batch = min(max_batch, self._batch_cap)
            close_at = time.monotonic() + self._max_wait_s
            while num_keys < max_batch:
                if self._queue:
                    nxt = self._peek_next()
                    if num_keys + len(nxt.keys) > max_batch:
                        break
                    self._pop_next()
                    batch.append(nxt)
                    num_keys += len(nxt.keys)
                    continue
                remaining = close_at - time.monotonic()
                if remaining <= 0 or self._closed:
                    break
                t_wait = time.monotonic()
                self._cond.wait(remaining)
                form_s += time.monotonic() - t_wait
            self._g_depth.set(len(self._queue))
        # Bubble attribution, outside the lock. An empty-queue wait
        # during which admission shed requests is idle the policy
        # manufactured, not absent demand — attribute it there.
        if util is not None:
            if empty_s > 0.0:
                cause = (
                    "admission_shed"
                    if self._c_shed.value - shed_before > 0
                    else "empty_queue"
                )
                self._util_idle(cause, empty_s)
            if form_s > 0.0:
                self._util_idle("batch_wait", form_s)
        return batch, time.monotonic() - t_first

    def _run(self) -> None:
        try:
            self._run_loop()
        finally:
            # Unblock the completion thread (and let it exit once the
            # handoff queue drains) no matter how the worker stopped.
            with self._complete_cond:
                self._worker_done = True
                self._complete_cond.notify_all()

    def _run_loop(self) -> None:
        while True:
            collected = self._collect()
            if collected is None:
                return
            batch, assembly_s = collected
            # Pre-dispatch deadline gate: requests that expired while
            # the batch was forming are dropped HERE, before any device
            # work, so an expired request never costs an evaluation.
            gate_ns = time.perf_counter_ns()
            now = time.monotonic()
            live = []
            for p in batch:
                if p.abandoned or (
                    p.deadline is not None and now > p.deadline
                ):
                    # Dropped unevaluated; the submitter raises
                    # DeadlineExceeded (and counts it) on its side.
                    self._c_expired_in_batch.inc()
                    self._release(p)
                    p.error = DeadlineExceeded("expired in queue")
                    p.event.set()
                    continue
                live.append(p)
            if not live:
                # Every request in the bucket died while batching:
                # skip padding, bucketing, and the device dispatch
                # entirely.
                self._c_batches_skipped.inc()
                continue
            flat = [k for p in live for k in p.keys]
            bucket = bucket_size(len(flat))
            multiple = self._key_multiple
            if bucket % multiple:
                bucket = -(-bucket // multiple) * multiple
            padded = flat + [flat[0]] * (bucket - len(flat))
            pad_waste = (bucket - len(flat)) / bucket
            if bucket in self._seen_buckets:
                self._c_hits.inc()
            else:
                self._seen_buckets.add(bucket)
                self._c_compiles.inc()
            self._c_batches.inc()
            self._c_pad.inc(bucket - len(flat))
            self._h_batch.observe(len(flat))
            self._h_pad_waste.observe(pad_waste)
            # Batch boundary: a pending snapshot flip applies HERE (or
            # not at all until the next batch), then the whole bucket
            # evaluates and binds against one generation.
            generation = None
            source = self._generation_source
            if source is not None:
                try:
                    generation = source.begin_batch()
                except Exception:  # noqa: BLE001 - rotation never kills serving
                    generation = None
            for p in live:
                p.generation = generation
            record = _BatchResult()
            record.live = live
            record.assembly_s = assembly_s
            record.bucket = bucket
            record.flat_len = len(flat)
            record.pad_waste = pad_waste
            record.generation = generation
            record.gate_ns = gate_ns
            try:
                # Chaos site: a worker-side fault here must fan out to
                # every live request and leave the worker serving.
                failpoints.fire("batcher.evaluate")
                telemetry = default_telemetry()
                h2d_before = _h2d_bytes(telemetry)
                sync_before = _sync_wait_ms(telemetry)
                tracker = telemetry.compile_tracker
                recorder = phases_mod.default_phase_recorder()
                with tracker.dispatch(
                    f"{self._name}.evaluate", shape_key(("k", bucket))
                ) as dispatch, tracing.span(
                    "batcher.evaluate", record=False, bucket=bucket,
                    keys=len(flat), party=self._party,
                ) as timing, recorder.collect() as batch_phases:
                    dispatch.timing = timing
                    # The batch-scoped record soaks up phase() brackets
                    # inside the evaluation path (h2d staging,
                    # compile-vs-compute in pir/server); the completion
                    # half re-attributes them to every live request.
                    results = list(self._evaluate(padded))
                record.eval_ms = timing.ms
                record.eval_ns = (timing.start_ns, timing.end_ns)
                self._h_evaluate.observe(record.eval_ms)
                # Utilization split: the evaluation wall is busy time
                # minus whatever it spent blocked in exposed transfer
                # syncs — those are `staging_sync` bubbles, so the
                # causes still sum to measured idle.
                if self._util is not None:
                    eval_s = record.eval_ms / 1e3
                    stall_s = min(eval_s, max(
                        0.0, _sync_wait_ms(telemetry) - sync_before
                    ) / 1e3)
                    self._util_busy(eval_s - stall_s)
                    self._util_idle("staging_sync", stall_s)
                record.results = results
                record.collected = (
                    batch_phases.snapshot()
                    if batch_phases is not None else {}
                )
                record.batch_phases = batch_phases
                # Measured worker-side, right after the evaluation
                # returns, so bucket N's staging traffic can never
                # bleed into bucket N-1's cost record on the
                # completion thread.
                record.transfer_bytes = max(
                    0, _h2d_bytes(telemetry) - h2d_before
                )
                if len(results) < len(flat):
                    raise RuntimeError(
                        f"evaluate returned {len(results)} results for "
                        f"{len(flat)} keys"
                    )
            except Exception as e:  # noqa: BLE001 - fan the error out
                record.error = e
            self._dispatch_complete(record)

    # -- completion half ----------------------------------------------------

    def _dispatch_complete(self, record: _BatchResult) -> None:
        """Hand an evaluated bucket to the completion half. Depth 1
        completes inline on the worker (pre-pipeline behavior);
        otherwise the handoff queue is bounded at depth-1 evaluated
        buckets, so the worker blocks rather than running unboundedly
        ahead of fan-out."""
        if self._completer is None:
            self._finish(record)
            return
        waited_s = 0.0
        with self._complete_cond:
            while len(self._complete_q) >= self._pipeline_depth - 1:
                t_wait = time.monotonic()
                self._complete_cond.wait()
                waited_s += time.monotonic() - t_wait
            self._complete_q.append(record)
            self._complete_cond.notify_all()
        # Worker blocked on the bounded handoff queue: the completion
        # half is the bottleneck, not the device feed.
        self._util_idle("pipeline_full", waited_s)

    def _complete_loop(self) -> None:
        while True:
            with self._complete_cond:
                while not self._complete_q and not self._worker_done:
                    self._complete_cond.wait()
                if not self._complete_q:
                    return
                record = self._complete_q.popleft()
                self._complete_cond.notify_all()
            t_finish = time.monotonic()
            try:
                self._finish(record)
                self._util_busy(
                    time.monotonic() - t_finish, thread="completer"
                )
            except Exception as e:  # noqa: BLE001 - never kill the completer
                for p in record.live:
                    if not p.event.is_set():
                        p.error = e
                        p.event.set()

    def _finish(self, rec: _BatchResult) -> None:
        """Complete one evaluated bucket: error/result fan-out, phase
        attribution, `end_batch`, cost-ledger feed. Runs inline on the
        worker at depth 1 and on the completion thread otherwise; reads
        only the `_BatchResult` snapshot, never live worker state."""
        if rec.error is not None:
            for p in rec.live:
                self._release(p)
                p.error = rec.error
                p.event.set()
            self._end_batch(rec.generation)
            return
        collected = rec.collected
        # Whatever the evaluation spent outside any phase bracket is
        # batcher/handler overhead: dispatch. Clamped at zero — when
        # the brackets over-cover the wall time the excess is recorded
        # as attribution slop instead of a negative residual.
        collected_ms = sum(collected.values())
        dispatch_ms = max(0.0, rec.eval_ms - collected_ms)
        slop_ms = max(0.0, collected_ms - rec.eval_ms)
        if slop_ms > 0.0:
            self._c_slop.inc(slop_ms)
        # Batch-level stage aggregates (once per batch) ...
        tracing.add_span(
            "batch_assembly", rec.assembly_s * 1e3, end_ns=rec.gate_ns,
            bucket=rec.bucket, batch_keys=rec.flat_len,
        )
        tracing.add_span(
            "device_compute", rec.eval_ms, start_ns=rec.eval_ns[0],
            end_ns=rec.eval_ns[1], pad_waste_ratio=round(rec.pad_waste, 4),
        )
        offset = 0
        done_ns = time.perf_counter_ns()
        for p in rec.live:
            p.result = rec.results[offset:offset + len(p.keys)]
            offset += len(p.keys)
            queue_wait_ms = (rec.gate_ns - p.t0_ns) / 1e6
            self._h_queue_wait.observe(queue_wait_ms)
            self._h_latency.observe((done_ns - p.t0_ns) / 1e6)
            tracing.log_span(
                "batcher.queue_wait", p.t0_ns, rec.gate_ns,
                parent=p.parent, trace=p.trace, party=self._party,
            )
            # ... and per-request spans grafted onto the submitting
            # thread's trace so /tracez decomposes each request.
            if p.trace is not None:
                p.trace.add_span("queue_wait", queue_wait_ms)
                p.trace.add_span(
                    "batch_assembly", rec.assembly_s * 1e3,
                    bucket=rec.bucket, batch_keys=rec.flat_len,
                )
                p.trace.add_span(
                    "device_compute", rec.eval_ms,
                    pad_waste_ratio=round(rec.pad_waste, 4),
                )
            if p.phases is not None:
                p.phases.add("queue", queue_wait_ms)
                p.phases.add("batch", rec.assembly_s * 1e3)
                p.phases.add_many(collected)
                p.phases.add("dispatch", dispatch_ms)
            self._release(p)
            p.event.set()
        # The batch has fully retired against its generation: let a
        # waiting flip proceed (and the old generation's stagings
        # drop once its last batch lands here).
        self._end_batch(rec.generation)
        # Terminal batch outcome: join the capacity-model estimate
        # for the executed bucket with the measured device truth
        # (after every waiter is released, so accounting adds no
        # request latency).
        self._observe_cost(rec)

    def _observe_cost(self, rec: _BatchResult) -> None:
        """Feed the cost ledger one (estimate, truth) pair for this
        batch. The estimate is what the capacity model would charge for
        the executed padded bucket (corrections included, so the
        recalibration loop is closed); the truth is the exclusive
        `device_compute` phase from the batch-scoped record, falling
        back to wall time minus compile when the evaluation path has no
        phase brackets (stub evaluators in tests). Never raises."""
        try:
            plan_meta = (
                rec.batch_phases.get_meta("serving_plan")
                if rec.batch_phases is not None else None
            ) or {}
            tier = str(plan_meta.get("mode", "unplanned"))
            actual_ms = rec.collected.get("device_compute", 0.0)
            if actual_ms <= 0.0:
                actual_ms = max(
                    0.0, rec.eval_ms - rec.collected.get("compile", 0.0)
                )
            pricer = self._cost_pricer
            if pricer is not None:
                predicted = pricer(rec.bucket)
            else:
                predicted = default_capacity_model().price_pir_keys(
                    rec.bucket
                )
            trace = next(
                (p.trace for p in rec.live if p.trace is not None), None
            )
            costmodel_mod.default_cost_ledger().observe(
                self._cost_workload, tier, str(rec.bucket),
                predicted_device_ms=predicted.device_ms,
                actual_device_ms=actual_ms,
                transfer_bytes=rec.transfer_bytes,
                trace=trace,
            )
        except Exception:  # noqa: BLE001 - accounting never breaks serving
            pass

    # -- lifecycle ----------------------------------------------------------

    def close(self, timeout: float = 10.0) -> None:
        """Drain the queue, then stop the worker — and, when pipelined,
        the completion thread, so every in-flight bucket fans out
        before close() returns. Subsequent submits raise."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout=timeout)
        if self._completer is not None:
            with self._complete_cond:
                self._worker_done = True
                self._complete_cond.notify_all()
            self._completer.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
