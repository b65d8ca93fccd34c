"""Served dense PIR: clients send single-index queries to the program's
serving sessions, and every answer is checked against the records.

Configuration keys (configs/<config>.json, `sizes`): `num_records`,
`record_bytes`, and an optional `mesh` [shard devices, key devices].

Traffic keys (traffic/<mix>.json):

    session         "leader_helper": a LeaderSession and a HelperSession
                    over one database, joined by an InProcessTransport
                    carrying the real hybrid encryption; a request is the
                    Leader's `handle_request`.
                    "plain": one PlainSession per party over one database;
                    a request sends its two halves to the two sessions.
    arrivals        see arrivals.py (poisson, bursty, closed)
    max_batch_size  the sessions' ServingConfig.max_batch_size
    pool            requests pre-built from the seed and cycled
    client_threads  open loop: threads that wait on answers in flight

Latency runs from the scheduled send time (open loop) or the send
(closed loop) to the answer. Every request scheduled inside the window
is awaited after it closes, and every answer is compared with the
reference (reference.py). A request the sessions refuse at admission
(`Overloaded`, a full queue) has no answer to compare: it counts in
`failed` and lowers `qps`, and does not make the run incorrect; any
other failure, or a request never answered, does.

`control=True` builds the control instead: the Leader with its own
degraded path switched on (`allow_degraded`) and a Helper leg that
always fails, so answers carry the Leader's share alone.
"""

from __future__ import annotations

import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import arrivals as arrivals_mod
import reference
import seeding

BATCHER_COUNTERS = (
    "requests_submitted", "batches", "padded_keys", "jit_bucket_compiles",
)
# Program counters that read 0 unless something stood in for the device
# path: the OOM-gated planner or mesh demotions, or a Leader answering
# with its own share alone.
FALLBACKS = ("pir.tier_demotions", "pir.mesh_fallbacks")
# How long after the window closes an answer may still come.
DRAIN_S = 60.0


def _buckets(max_batch: int):
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [b]


class Driver:
    def __init__(self, config, traffic, seed, log, control=False):
        self.sizes = config["sizes"]
        self.traffic = traffic
        self.seed = int(seed)
        self.log = log
        self.control = control
        self.sessions = []
        self._helper_legs = []
        self._recording = False

    # -- set-up -------------------------------------------------------------

    def setup(self):
        from distributed_point_functions_tpu.pir import messages
        from distributed_point_functions_tpu.pir.client import (
            ENCRYPTION_CONTEXT_INFO,
            DenseDpfPirClient,
        )
        from distributed_point_functions_tpu.pir.database import (
            DenseDpfPirDatabase,
        )
        from distributed_point_functions_tpu.serving import (
            HelperSession,
            LeaderSession,
            PlainSession,
            ServingConfig,
        )
        from distributed_point_functions_tpu.serving.transport import (
            InProcessTransport,
            Transport,
            TransportError,
        )
        from distributed_point_functions_tpu.testing import encrypt_decrypt

        n, width = int(self.sizes["num_records"]), int(self.sizes["record_bytes"])
        t0 = time.perf_counter()
        raw = seeding.new_rng(self.seed, seeding.RECORDS).bytes(n * width)
        self.table = np.frombuffer(raw, dtype=np.uint8).reshape(n, width)
        database = DenseDpfPirDatabase(
            [raw[i * width:(i + 1) * width] for i in range(n)]
        )
        self.log(f"database: {n} records x {width} B in "
                 f"{time.perf_counter() - t0:.1f} s")

        mesh = None
        if self.sizes.get("mesh"):
            from distributed_point_functions_tpu.parallel import make_mesh2d

            mesh = make_mesh2d(*self.sizes["mesh"])
        self.max_batch = int(self.traffic["max_batch_size"])
        knobs = {"max_batch_size": self.max_batch}
        self.kind = self.traffic["session"]
        if self.kind == "leader_helper":
            if self.control:
                class _Down(Transport):
                    def roundtrip(self, payload, timeout=None, on_sent=None):
                        raise TransportError("helper leg switched off")

                leader_config = ServingConfig(
                    **knobs, allow_degraded=True, helper_retries=0
                )
                self.leader = LeaderSession(
                    database, _Down(), leader_config, mesh=mesh
                )
                self.sessions = [("leader", self.leader)]
            else:
                config = ServingConfig(**knobs)
                helper = HelperSession(
                    database, encrypt_decrypt.decrypt, config, mesh=mesh
                )
                self._helper_wire = helper.handle_wire
                self.leader = LeaderSession(
                    database, InProcessTransport(self._timed_helper), config,
                    mesh=mesh,
                )
                self.sessions = [("leader", self.leader), ("helper", helper)]
        elif self.kind == "plain":
            if self.control:
                raise ValueError("the control is defined for leader_helper")
            config = ServingConfig(**knobs)
            self.sessions = [
                (f"party{p}", PlainSession(database, config, mesh=mesh))
                for p in (0, 1)
            ]
        else:
            raise ValueError(f"unknown session kind {self.kind!r}")

        # The request pool: indices, keys and one-time pads from the seed.
        t0 = time.perf_counter()
        rng = seeding.new_rng(self.seed, seeding.REQUESTS)
        size = max(int(self.traffic["pool"]), self.max_batch)
        self.indices = [int(i) for i in rng.integers(0, n, size)]
        client = DenseDpfPirClient.create(n, encrypt_decrypt.encrypt)
        with seeding.seeded_secrets(rng):
            keys0, keys1 = client.dpf.generate_keys_batch(
                [i // 128 for i in self.indices],
                [1 << (i % 128) for i in self.indices],
            )
            self.pads = [rng.bytes(16) for _ in self.indices]
        self.party_keys = [keys0, keys1]
        if self.kind == "leader_helper":
            self.requests = []
            for k0, k1, pad in zip(keys0, keys1, self.pads):
                helper_request = messages.HelperRequest(
                    plain_request=messages.PlainRequest(dpf_keys=[k1]),
                    one_time_pad_seed=pad,
                )
                ciphertext = encrypt_decrypt.encrypt(
                    messages.serialize_helper_request(client.dpf, helper_request),
                    ENCRYPTION_CONTEXT_INFO,
                )
                self.requests.append(messages.PirRequest(
                    leader_request=messages.LeaderRequest(
                        plain_request=messages.PlainRequest(dpf_keys=[k0]),
                        encrypted_helper_request=messages.EncryptedHelperRequest(
                            encrypted_request=ciphertext
                        ),
                    )
                ))
        else:
            self.requests = [
                tuple(
                    messages.PirRequest(
                        plain_request=messages.PlainRequest(dpf_keys=[k])
                    )
                    for k in pair
                )
                for pair in zip(keys0, keys1)
            ]
        self.log(f"request pool: {size} requests in "
                 f"{time.perf_counter() - t0:.1f} s")

    def _timed_helper(self, payload: bytes) -> bytes:
        t0 = time.perf_counter()
        try:
            return self._helper_wire(payload)
        finally:
            if self._recording:
                t1 = time.perf_counter()
                self._helper_legs.append((t1, (t1 - t0) * 1e3))

    def warm(self):
        """Compile every bucket the window can form (1 .. max_batch_size,
        powers of two) through the entry the batcher calls, all buckets
        at once so that their compiles overlap; then send each bucket
        through each party's batcher, so the batchers know them too; then
        a burst of concurrent requests end to end."""
        buckets = _buckets(self.max_batch)
        first = self.sessions[0][1]
        keys = self.party_keys[0]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(buckets)) as pool:
            list(pool.map(lambda b: first._evaluate_keys(keys[:b]), buckets))
        self.log(f"compiled buckets {buckets} in "
                 f"{time.perf_counter() - t0:.1f} s")
        for (_, session), party in zip(self.sessions, self.party_keys):
            for b in buckets:
                session.batcher.submit(party[:b])
        with ThreadPoolExecutor(16) as pool:
            list(pool.map(self._serve, range(4 * self.max_batch)))

    # -- window -------------------------------------------------------------

    def _serve(self, j: int):
        req = self.requests[j % len(self.requests)]
        if self.kind == "leader_helper":
            return self.leader.handle_request(req)
        return tuple(
            session.handle_request(r)
            for (_, session), r in zip(self.sessions, req)
        )

    def counters(self):
        """Each party's batcher counters and batches by padded bucket."""
        out = {}
        for name, session in self.sessions:
            exported = session.metrics.export()
            prefix = "plain.batcher" if self.kind == "plain" else f"{name}.batcher"
            counters = exported["counters"]
            hist = exported["histograms"].get(f"{prefix}.batch_keys", {})
            out[name] = {
                **{c: counters.get(f"{prefix}.{c}", 0) for c in BATCHER_COUNTERS},
                "bucket_batches": dict(hist.get("buckets", {})),
            }
        return out

    @staticmethod
    def delta(after, before):
        return {
            name: {
                **{c: after[name][c] - before[name][c] for c in BATCHER_COUNTERS},
                "bucket_batches": {
                    b: n - before[name]["bucket_batches"].get(b, 0)
                    for b, n in after[name]["bucket_batches"].items()
                },
            }
            for name in after
        }

    def window(self, seconds: float) -> dict:
        sched = arrivals_mod.schedule(
            self.traffic["arrivals"], seconds,
            seeding.new_rng(self.seed, seeding.ARRIVALS),
        )
        before = self.counters()
        self.results = []
        self._helper_legs = []
        self._recording = True
        t0 = time.perf_counter()
        if sched is None:
            self._closed_loop(seconds, t0)
        else:
            self._open_loop(sched, t0)
        t_close = t0 + seconds
        deadline = t_close + DRAIN_S
        for entry in self.results:
            entry["event"].wait(max(0.0, deadline - time.perf_counter()))
        self._recording = False
        after = self.counters()
        done = [r for r in self.results if r["event"].is_set()]
        answered = [r for r in done if r["error"] is None]
        failed = [r for r in self.results if r["error"] is not None]
        for r in failed[:1]:
            self.log("first failed request:\n" + r["error"])
        last = max((r["done"] for r in done), default=t_close)
        return {
            "seconds": seconds,
            "attempted": len(self.results),
            "failed": len(self.results) - len(answered),
            "refused": sum(r["refused"] for r in self.results),
            "latencies_ms": [(r["done"] - r["due"]) * 1e3 for r in answered],
            "late_ms": [r["late"] * 1e3 for r in self.results],
            "drain_s": max(last - t0, seconds),
            "helper_legs": list(self._helper_legs),  # (end time, ms)
            "batchers": self.delta(after, before),
            "shapes": {
                "num_records": int(self.sizes["num_records"]),
                "record_bytes": int(self.sizes["record_bytes"]),
            },
        }

    def _run_one(self, entry):
        from distributed_point_functions_tpu.serving.batcher import Overloaded

        try:
            entry["answer"] = self._serve(entry["j"])
        except Exception as e:  # noqa: BLE001 - counted as failed, reported
            entry["error"] = traceback.format_exc()
            entry["refused"] = isinstance(e, Overloaded)
        entry["done"] = time.perf_counter()
        entry["event"].set()

    def _entry(self, j, due):
        return {"j": j, "due": due, "late": 0.0, "answer": None,
                "error": None, "refused": False, "done": None,
                "event": threading.Event()}

    def _open_loop(self, sched, t0):
        threads = int(self.traffic.get("client_threads", 512))
        with ThreadPoolExecutor(threads) as pool:
            for j, offset in enumerate(sched):
                due = t0 + offset
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                entry = self._entry(j, due)
                entry["late"] = max(0.0, time.perf_counter() - due)
                self.results.append(entry)
                pool.submit(self._run_one, entry)

    def _closed_loop(self, seconds, t0):
        clients = int(self.traffic["arrivals"]["clients"])
        lock = threading.Lock()
        counter = [0]
        end = t0 + seconds

        def client():
            while True:
                now = time.perf_counter()
                if now >= end:
                    return
                with lock:
                    j = counter[0]
                    counter[0] += 1
                    entry = self._entry(j, now)
                    self.results.append(entry)
                self._run_one(entry)

        workers = [threading.Thread(target=client) for _ in range(clients)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(DRAIN_S + seconds)

    # -- correctness --------------------------------------------------------

    def check(self, record: dict) -> list:
        """[(name, value, limit)]: answers that decode to another record,
        requests that failed (other than by a refusal at admission) or
        were never answered, and fallback counters."""
        from distributed_point_functions_tpu.observability import tracing

        wrong = 0
        for r in self.results:
            if r["error"] is not None or r["answer"] is None:
                continue
            k = r["j"] % len(self.requests)
            index = self.indices[k]
            if self.kind == "leader_helper":
                got = r["answer"].dpf_pir_response.masked_response
                want = [reference.masked_record(self.table, index, self.pads[k])]
            else:
                a, b = (x.dpf_pir_response.masked_response for x in r["answer"])
                got = [
                    (np.frombuffer(x, np.uint8) ^ np.frombuffer(y, np.uint8))
                    .tobytes() for x, y in zip(a, b)
                ]
                want = [self.table[index].tobytes()]
            wrong += got != want
        fallbacks = sum(tracing.runtime_counters.get(c) or 0 for c in FALLBACKS)
        return [
            ("wrong_answers", wrong, 0),
            ("unanswered", record["failed"] - record["refused"], 0),
            ("fallbacks", fallbacks, 0),
        ]

    def close(self):
        for _, session in self.sessions:
            session.close()
        self.results = []
