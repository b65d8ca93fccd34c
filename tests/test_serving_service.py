"""Serving session tests: batched correctness, retries, degradation.

Small database (128 x 16B) so the per-bucket jit compiles stay cheap;
the protocol mechanics (hybrid encryption, OTP masking, share
combination) are the real ones from pir/ and crypto/.
"""

import os
import threading
import time

import numpy as np
import pytest

from distributed_point_functions_tpu.pir import (
    DenseDpfPirClient,
    DenseDpfPirDatabase,
    DenseDpfPirServer,
)
from distributed_point_functions_tpu.serving import (
    DeadlineExceeded,
    HelperSession,
    HelperUnavailable,
    InProcessTransport,
    LeaderSession,
    PlainSession,
    ServingConfig,
    TransportError,
    TransportTimeout,
)
from distributed_point_functions_tpu.testing import encrypt_decrypt

NUM_RECORDS = 128
RECORD_BYTES = 16
RNG = np.random.default_rng(1234)


def build_database():
    records = [
        bytes(RNG.integers(0, 256, RECORD_BYTES, dtype=np.uint8))
        for _ in range(NUM_RECORDS)
    ]
    builder = DenseDpfPirDatabase.Builder()
    for r in records:
        builder.insert(r)
    return builder.build(), records


DATABASE, RECORDS = build_database()


def make_config(**overrides):
    base = dict(
        max_batch_size=4,
        max_wait_ms=5.0,
        helper_timeout_ms=None,
        helper_retries=2,
        helper_backoff_ms=1.0,
        helper_backoff_max_ms=2.0,
    )
    base.update(overrides)
    return ServingConfig(**base)


# ---------------------------------------------------------------------------
# PlainSession: batched results == unbatched oracle, bounded compiles
# ---------------------------------------------------------------------------


def test_plain_session_bit_identical_to_unbatched_oracle():
    client = DenseDpfPirClient.create(NUM_RECORDS, lambda pt, ci: pt)
    indices = [[3], [77], [12, 99], [0]]
    requests = [client.create_plain_requests(ix)[0] for ix in indices]
    oracle_server = DenseDpfPirServer.create_plain(DATABASE)
    oracle = [
        oracle_server.handle_plain_request(r).dpf_pir_response.masked_response
        for r in requests
    ]

    with PlainSession(DATABASE, make_config()) as session:
        results = [None] * len(requests)

        def worker(i):
            results[i] = session.handle_request(requests[i])

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(len(requests))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        counters = session.metrics.export()["counters"]

    for got, want in zip(results, oracle):
        assert got.dpf_pir_response.masked_response == want
    # Mixed sizes 1 and 2 over a max batch of 4: at most log2(4)+1 = 3
    # distinct jit shape buckets, counted via the metrics registry.
    assert 1 <= counters["plain.batcher.jit_bucket_compiles"] <= 3
    assert counters["plain.batcher.requests_submitted"] == len(requests)


def test_plain_session_unbatched_mode_matches_too():
    client = DenseDpfPirClient.create(NUM_RECORDS, lambda pt, ci: pt)
    request = client.create_plain_requests([42])[0]
    oracle_server = DenseDpfPirServer.create_plain(DATABASE)
    want = oracle_server.handle_plain_request(
        request
    ).dpf_pir_response.masked_response
    with PlainSession(DATABASE, make_config(batching=False)) as session:
        got = session.handle_request(request)
    assert got.dpf_pir_response.masked_response == want


def test_expired_deadline_rejected_without_evaluating():
    client = DenseDpfPirClient.create(NUM_RECORDS, lambda pt, ci: pt)
    request = client.create_plain_requests([5])[0]
    with PlainSession(DATABASE, make_config()) as session:
        with pytest.raises(DeadlineExceeded):
            session.handle_request(
                request, deadline=time.monotonic() - 0.001
            )
        counters = session.metrics.export()["counters"]
    assert counters["plain.batcher.requests_deadline_exceeded"] == 1


# ---------------------------------------------------------------------------
# Leader/Helper end-to-end with fault injection on the helper leg
# ---------------------------------------------------------------------------


class FlakyTransport(InProcessTransport):
    """Fails the first `failures` round trips, then behaves."""

    def __init__(self, handler, failures, exc=TransportTimeout):
        super().__init__(handler)
        self.failures = failures
        self.exc = exc
        self.attempts = 0

    def roundtrip(self, payload, timeout=None, on_sent=None):
        self.attempts += 1
        if self.attempts <= self.failures:
            raise self.exc("injected helper fault")
        return super().roundtrip(payload, timeout, on_sent)


def leader_helper_pair(transport_factory, leader_config=None):
    helper = HelperSession(
        DATABASE, encrypt_decrypt.decrypt, make_config()
    )
    leader = LeaderSession(
        DATABASE,
        transport_factory(helper.handle_wire),
        leader_config if leader_config is not None else make_config(),
    )
    return leader, helper


def run_query(leader, indices):
    client = DenseDpfPirClient.create(NUM_RECORDS, encrypt_decrypt.encrypt)
    request, state = client.create_request(indices)
    response = leader.handle_request(request)
    return client.handle_response(response, state)


def test_leader_helper_end_to_end_clean_path():
    leader, helper = leader_helper_pair(InProcessTransport)
    with helper, leader:
        got = run_query(leader, [3, 42, 127])
        counters = leader.metrics.export()["counters"]
    assert got == [RECORDS[3], RECORDS[42], RECORDS[127]]
    assert counters["leader.helper_retries"] == 0
    assert counters["leader.helper_failures"] == 0


def test_helper_timeout_then_retry_then_success():
    transports = []

    def factory(handler):
        t = FlakyTransport(handler, failures=2)
        transports.append(t)
        return t

    leader, helper = leader_helper_pair(factory)
    with helper, leader:
        got = run_query(leader, [7, 77])
        counters = leader.metrics.export()["counters"]
    assert got == [RECORDS[7], RECORDS[77]]
    assert transports[0].attempts == 3
    assert counters["leader.helper_retries"] == 2
    assert counters["leader.helper_timeouts"] == 2
    assert counters["leader.helper_failures"] == 0


def test_helper_permanently_down_raises_helper_unavailable():
    def factory(handler):
        return FlakyTransport(handler, failures=10**9, exc=TransportError)

    leader, helper = leader_helper_pair(factory)
    with helper, leader:
        with pytest.raises(HelperUnavailable):
            run_query(leader, [9])
        counters = leader.metrics.export()["counters"]
    # First attempt + helper_retries, then permanent failure.
    assert counters["leader.helper_retries"] == 2
    assert counters["leader.helper_failures"] == 1
    assert counters["leader.degraded_responses"] == 0


def test_helper_permanently_down_degraded_mode_keeps_answering():
    def factory(handler):
        return FlakyTransport(handler, failures=10**9)

    leader, helper = leader_helper_pair(
        factory, leader_config=make_config(allow_degraded=True)
    )
    client = DenseDpfPirClient.create(NUM_RECORDS, encrypt_decrypt.encrypt)
    request, _ = client.create_request([11])
    with helper, leader:
        response = leader.handle_request(request)
        counters = leader.metrics.export()["counters"]
    # The degraded response is the Leader's share only — a liveness
    # signal, NOT the record (the Helper's share is missing by
    # construction, so the payload must differ from the true record).
    masked = response.dpf_pir_response.masked_response
    assert len(masked) == 1
    assert masked[0] != RECORDS[11]
    assert counters["leader.degraded_responses"] == 1
    assert counters["leader.helper_failures"] == 1


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_rule(tmp_path, monkeypatch, env_dir):
    """One rule: JAX_COMPILATION_CACHE_DIR when set (JAX reads it; the
    helper sets nothing), else the fixed <repo>/.jax_cache."""
    import jax

    from distributed_point_functions_tpu.serving import service

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(service, "_compile_cache_state", None)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        state = service.install_compile_cache()
        if env_dir:
            assert state["dir"] == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__
            )))
            want = os.path.join(repo, ".jax_cache")
            assert state["dir"] == want == service.REPO_COMPILE_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
