"""Streaming fused expand->inner-product serving pipeline tests.

Differential coverage of `dense_eval_planes_v2.streaming_pir_inner_products_v2`
against the materialized selection-matrix path (the oracle), the serving
planner's mode/budget model (`pir/planner.py`), the server-level dispatch,
the chunk-sharded mesh variant, the hierarchical-geometry tail-kernel
verdict, and the database staging locks. All CPU-runnable (tier-1).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_point_functions_tpu.ops.inner_product import (
    xor_inner_product,
    xor_inner_product_accumulate,
)
from distributed_point_functions_tpu.pir import messages
from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
from distributed_point_functions_tpu.pir.database import DenseDpfPirDatabase
from distributed_point_functions_tpu.pir.dense_eval import (
    evaluate_selection_blocks,
    stage_keys,
)
from distributed_point_functions_tpu.pir.dense_eval_planes_v2 import (
    bitrev_permutation,
    streaming_block_order,
    streaming_block_permute_records,
    streaming_pir_inner_products_v2,
)
from distributed_point_functions_tpu.pir.planner import (
    CHUNK_GRANULE_LEVELS,
    chunked_selection_bytes,
    materialized_selection_bytes,
    plan_dense_serving,
    streaming_ip,
    streaming_selection_bytes,
)
from distributed_point_functions_tpu.pir.server import DenseDpfPirServer
from distributed_point_functions_tpu.prng import xor_bytes

RNG = np.random.default_rng(77)


def _staged_batch(num_records, indices):
    """Client keys for `indices`, staged, plus the tree split the server
    uses: (staged, walk_levels, expand_levels)."""
    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    keys0, keys1 = client._generate_key_pairs(list(indices))
    staged = stage_keys(keys0)
    total = staged[2].shape[0]
    num_blocks = -(-num_records // 128)
    expand = max(0, (num_blocks - 1).bit_length())
    return staged, total - expand, expand, keys0, keys1


def _oracle(db, staged, walk_levels, expand_levels):
    """Materialized path over the full padded (covering) domain."""
    sel = evaluate_selection_blocks(
        *staged,
        walk_levels=walk_levels,
        expand_levels=expand_levels,
        num_blocks=1 << expand_levels,
    )
    return np.asarray(
        xor_inner_product(jnp.asarray(db._host_words_padded()), sel)
    )


# ---------------------------------------------------------------------------
# Streaming block-order algebra
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("e,cut", [(4, 0), (4, 2), (4, 4), (5, 3), (1, 1)])
def test_streaming_block_order_is_involution(e, cut):
    """position -> natural-block is its own inverse (both factors are
    bit reversals), so one gather stages and one gather un-stages."""
    order = streaming_block_order(e, cut)
    assert np.array_equal(order[order], np.arange(1 << e))


def test_streaming_block_order_degenerate_cuts_are_plain_bitrev():
    """cut=0 (whole tree is one chunk) and cut=e (one block per chunk)
    both collapse to the full bit-reversal the bitrev staging uses."""
    for e in (3, 5):
        full = np.asarray(bitrev_permutation(e))
        assert np.array_equal(streaming_block_order(e, 0), full)
        assert np.array_equal(streaming_block_order(e, e), full)


def test_streaming_block_permute_rejects_bad_geometry():
    with pytest.raises(ValueError, match="multiple of 128"):
        streaming_block_permute_records(np.zeros((100, 2), np.uint32), 1)
    with pytest.raises(ValueError, match="power of two"):
        streaming_block_permute_records(np.zeros((3 * 128, 2), np.uint32), 1)
    with pytest.raises(ValueError, match="cut_levels"):
        streaming_block_order(2, 3)


def test_xor_inner_product_accumulate_partitions():
    """XOR-accumulating per-span partials equals the whole-db product
    (the identity the streaming scan relies on)."""
    db = RNG.integers(0, 1 << 32, (512, 3), dtype=np.uint32)
    sel = RNG.integers(0, 1 << 32, (4, 4, 4), dtype=np.uint32)
    whole = np.asarray(xor_inner_product(jnp.asarray(db), jnp.asarray(sel)))
    acc = jnp.zeros((4, 3), jnp.uint32)
    for c in range(4):
        acc = xor_inner_product_accumulate(
            acc,
            jnp.asarray(db[c * 128:(c + 1) * 128]),
            jnp.asarray(sel[:, c:c + 1]),
        )
    np.testing.assert_array_equal(np.asarray(acc), whole)


# ---------------------------------------------------------------------------
# Streaming vs materialized differential (the tentpole's correctness bar)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "num_records,size,nq,cuts",
    [
        # Full split sweep incl. cut=0 (chunk == whole domain) and
        # chunk_levels=0 (chunk == one block).
        (1000, 8, 5, (0, 1, 2, 3)),
        # Multi-word records; batch of one. The chunk-boundary edges are
        # covered above — one mid split each keeps the CPU tier-1 cost
        # bounded (every (cut, shapes) pair is its own scan compile).
        (384, 256, 33, (1,)),
        (1500, 8, 1, (2,)),
    ],
)
def test_streaming_matches_materialized_cut_sweep(num_records, size, nq, cuts):
    """Bit-identical inner products across cut/chunk splits."""
    records = [RNG.bytes(size) for _ in range(num_records)]
    db = DenseDpfPirDatabase(records)
    indices = [int(i) for i in RNG.integers(0, num_records, nq)]
    staged, walk, e, _, _ = _staged_batch(num_records, indices)
    want = _oracle(db, staged, walk, e)

    for cut in cuts:
        chunks = db.streaming_chunks(cut_levels=cut, bitmajor=False)
        got = np.asarray(
            streaming_pir_inner_products_v2(
                *staged,
                chunks,
                walk_levels=walk,
                cut_levels=cut,
                chunk_levels=e - cut,
                ip="jnp",
            )
        )
        np.testing.assert_array_equal(got, want, err_msg=f"cut={cut}")


def test_streaming_large_batch_matches_materialized():
    """q=128 batch (the bench's headline batch size) through one split."""
    num_records, nq = 1000, 128
    records = [RNG.bytes(8) for _ in range(num_records)]
    db = DenseDpfPirDatabase(records)
    indices = [int(i) for i in RNG.integers(0, num_records, nq)]
    staged, walk, e, _, _ = _staged_batch(num_records, indices)
    want = _oracle(db, staged, walk, e)
    chunks = db.streaming_chunks(cut_levels=1, bitmajor=False)
    got = np.asarray(
        streaming_pir_inner_products_v2(
            *staged,
            chunks,
            walk_levels=walk,
            cut_levels=1,
            chunk_levels=e - 1,
            ip="jnp",
        )
    )
    np.testing.assert_array_equal(got, want)


def test_streaming_pallas2_interpret_matches_jnp():
    """The MXU scan tier (bit-major staging + pallas2 accumulate) is
    bit-identical to the jnp scan tier (interpret mode: no Mosaic)."""
    num_records, nq = 512, 3
    records = [RNG.bytes(12) for _ in range(num_records)]
    db = DenseDpfPirDatabase(records)
    indices = [0, 511, 200]
    staged, walk, e, _, _ = _staged_batch(num_records, indices)
    want = _oracle(db, staged, walk, e)
    kwargs = dict(walk_levels=walk, cut_levels=1, chunk_levels=e - 1)
    got = np.asarray(
        streaming_pir_inner_products_v2(
            *staged,
            db.streaming_chunks(cut_levels=1, bitmajor=True),
            ip="pallas2",
            interpret=True,
            **kwargs,
        )
    )
    np.testing.assert_array_equal(got, want)


def test_streaming_validates_plan_geometry():
    records = [RNG.bytes(8) for _ in range(256)]
    db = DenseDpfPirDatabase(records)
    staged, walk, e, _, _ = _staged_batch(256, [1])
    chunks = db.streaming_chunks(cut_levels=1, bitmajor=False)
    with pytest.raises(ValueError, match="correction levels"):
        streaming_pir_inner_products_v2(
            *staged, chunks, walk_levels=walk, cut_levels=1, chunk_levels=e
        )
    with pytest.raises(ValueError, match="database chunks"):
        streaming_pir_inner_products_v2(
            *staged, chunks, walk_levels=walk, cut_levels=0, chunk_levels=e
        )


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


def test_plan_materialized_when_under_budget():
    plan = plan_dense_serving(
        num_keys=4, num_blocks=8, expand_levels=3, budget_bytes=1 << 20
    )
    assert plan.mode == "materialized"
    assert plan.selection_bytes_peak == materialized_selection_bytes(4, 8)
    assert plan.selection_bytes_peak <= plan.budget_bytes


def test_plan_streaming_over_budget_fits_model():
    """Over-budget + covering tree -> streaming, and the chosen split's
    modeled peak respects the budget (the acceptance bound) while
    maximizing chunk_levels."""
    nq, e = 20, 4
    budget = 4000  # mat = 20*16*16B = 5120 > budget
    plan = plan_dense_serving(
        num_keys=nq,
        num_blocks=16,
        expand_levels=e,
        serving_bitrev=True,
        budget_bytes=budget,
    )
    assert plan.mode == "streaming"
    assert plan.cut_levels + plan.chunk_levels == e
    assert plan.num_chunks == 1 << plan.cut_levels
    assert plan.selection_bytes_peak == streaming_selection_bytes(
        nq, plan.cut_levels, plan.chunk_levels
    )
    assert plan.selection_bytes_peak <= budget
    # Largest feasible chunk: every bigger split must overflow the budget.
    for r in range(plan.chunk_levels + 1, e + 1):
        assert streaming_selection_bytes(nq, e - r, r) > budget


def test_plan_streaming_infeasible_budget_minimizes_peak():
    """When no split fits, the planner still streams (each scan step is
    strictly smaller than the materialized tensor) at the peak-minimizing
    split."""
    nq, e = 5, 4
    budget = 256
    plan = plan_dense_serving(
        num_keys=nq, num_blocks=12, expand_levels=e, budget_bytes=budget
    )
    assert plan.mode == "streaming"
    best = min(
        streaming_selection_bytes(nq, e - r, r) for r in range(e + 1)
    )
    assert plan.selection_bytes_peak == best
    assert plan.selection_bytes_peak < materialized_selection_bytes(
        nq, 1 << e
    )


def test_plan_env_gates(monkeypatch):
    kwargs = dict(num_keys=5, num_blocks=12, expand_levels=4, budget_bytes=256)
    monkeypatch.setenv("DPF_TPU_STREAMING", "0")
    plan = plan_dense_serving(**kwargs)
    assert plan.mode == "chunked"
    assert chunked_selection_bytes(5, plan.chunk_levels) == (
        plan.selection_bytes_peak
    )
    monkeypatch.setenv("DPF_TPU_STREAMING", "1")
    under = plan_dense_serving(
        num_keys=1, num_blocks=12, expand_levels=4, budget_bytes=1 << 20
    )
    assert under.mode == "streaming"  # forced even under budget


def test_plan_chunked_when_tree_cannot_cover():
    """A domain smaller than the database (blocks > 2^expand_levels) has
    no streaming staging; the legacy chunked loop serves it."""
    plan = plan_dense_serving(
        num_keys=64, num_blocks=40, expand_levels=3, budget_bytes=1024
    )
    assert plan.mode == "chunked"
    assert plan.chunk_levels <= CHUNK_GRANULE_LEVELS


def test_streaming_ip_resolution(monkeypatch):
    monkeypatch.delenv("DPF_TPU_STREAMING_IP", raising=False)
    assert streaming_ip("tpu") == "pallas2"
    assert streaming_ip("cpu") == "jnp"
    monkeypatch.setenv("DPF_TPU_STREAMING_IP", "jnp")
    assert streaming_ip("tpu") == "jnp"


# ---------------------------------------------------------------------------
# Server-level dispatch
# ---------------------------------------------------------------------------


def test_streaming_serving_matches_materialized_server(monkeypatch):
    """With a tiny selection budget the planner streams; responses must
    be byte-identical to the materialized pipeline, and the two parties'
    shares must still reconstruct the records."""
    num_records = 1500  # 12 blocks -> covering tree of 16
    records = [RNG.bytes(20) for _ in range(num_records)]
    plain = DenseDpfPirServer.create_plain(DenseDpfPirDatabase(records))
    streaming = DenseDpfPirServer.create_plain(DenseDpfPirDatabase(records))

    indices = [0, 77, 1499, 640, 1024]
    _, _, _, keys0, keys1 = _staged_batch(num_records, indices)
    req0 = messages.PirRequest(
        plain_request=messages.PlainRequest(dpf_keys=list(keys0))
    )
    req1 = messages.PirRequest(
        plain_request=messages.PlainRequest(dpf_keys=list(keys1))
    )
    want = plain.handle_plain_request(req0).dpf_pir_response.masked_response

    monkeypatch.setenv("DPF_TPU_SELECTION_BYTES_BUDGET", "256")
    plan = streaming._plan_serving(len(indices), False)
    assert plan.mode == "streaming"
    got = streaming.handle_plain_request(req0).dpf_pir_response.masked_response
    assert got == want

    r1 = streaming.handle_plain_request(req1).dpf_pir_response.masked_response
    for q, idx in enumerate(indices):
        assert xor_bytes(got[q], r1[q]) == records[idx]


def test_streaming_disabled_falls_back_to_chunked(monkeypatch):
    """DPF_TPU_STREAMING=0 + over budget keeps the legacy chunked loop,
    byte-identical as before."""
    num_records = 1500
    records = [RNG.bytes(20) for _ in range(num_records)]
    plain = DenseDpfPirServer.create_plain(DenseDpfPirDatabase(records))
    chunked = DenseDpfPirServer.create_plain(DenseDpfPirDatabase(records))
    indices = [3, 800, 1499]
    _, _, _, keys0, _ = _staged_batch(num_records, indices)
    req = messages.PirRequest(
        plain_request=messages.PlainRequest(dpf_keys=list(keys0))
    )
    want = plain.handle_plain_request(req).dpf_pir_response.masked_response
    monkeypatch.setenv("DPF_TPU_SELECTION_BYTES_BUDGET", "256")
    monkeypatch.setenv("DPF_TPU_STREAMING", "0")
    assert chunked._plan_serving(len(indices), False).mode == "chunked"
    got = chunked.handle_plain_request(req).dpf_pir_response.masked_response
    assert got == want


def test_streaming_ip_failure_raises(monkeypatch):
    """A crash in the pallas2 scan tier raises out of serving: the jnp
    scan never answers in its place, and nothing is demoted."""
    import warnings

    from distributed_point_functions_tpu.observability import tracing

    num_records = 1000
    records = [RNG.bytes(8) for _ in range(num_records)]
    server = DenseDpfPirServer.create_plain(DenseDpfPirDatabase(records))
    _, _, _, keys0, _ = _staged_batch(num_records, [5, 999])
    req = messages.PirRequest(
        plain_request=messages.PlainRequest(dpf_keys=list(keys0))
    )
    monkeypatch.setenv("DPF_TPU_SELECTION_BYTES_BUDGET", "64")
    monkeypatch.setenv("DPF_TPU_STREAMING_IP", "pallas2")
    before = tracing.runtime_counters.get("pir.tier_demotions")
    # pallas2's compiled path raises on CPU long before Mosaic — the
    # same contract as a TPU compile crash.
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with pytest.raises(Exception) as info:
            server.handle_plain_request(req)
    assert not isinstance(info.value, Warning)
    assert not [w for w in caught if "fall" in str(w.message)]
    assert server._plan_serving(2, False).ip == "pallas2"
    assert tracing.runtime_counters.get("pir.tier_demotions") == before


# ---------------------------------------------------------------------------
# Chunk-sharded mesh variant
# ---------------------------------------------------------------------------


def test_sharded_streaming_matches_oracle():
    from distributed_point_functions_tpu.parallel.sharded import (
        make_mesh,
        sharded_dense_pir_step_streaming,
        stage_streaming_chunks,
    )

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(8)
    num_records, nq = 1024, 9  # 8 blocks -> cut=3 gives one chunk/device
    records = [RNG.bytes(16) for _ in range(num_records)]
    db = DenseDpfPirDatabase(records)
    indices = [int(i) for i in RNG.integers(0, num_records, nq)]
    staged, walk, e, _, _ = _staged_batch(num_records, indices)
    want = _oracle(db, staged, walk, e)

    step = sharded_dense_pir_step_streaming(
        mesh, walk_levels=walk, cut_levels=3, chunk_levels=e - 3, ip="jnp"
    )
    chunks = stage_streaming_chunks(
        mesh, db.streaming_chunks(cut_levels=3, bitmajor=False)
    )
    got = np.asarray(step(*staged, chunks))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Hierarchical expansion dispatch (dpf.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "mode,tail_kind,has_tail,head_req",
    [("walk", "walk", True, -1), ("tail", None, True, 0),
     ("pallas", None, False, 0)],
)
def test_dpf_expand_dispatch_follows_mode(
    monkeypatch, mode, tail_kind, has_tail, head_req
):
    """dpf's fused expansion takes the level-kernel mode as given: walk
    runs the walk kernels with an auto head, tail adds the fused tail,
    pallas runs the per-level kernels alone — no geometry gate swaps
    one for another."""
    from distributed_point_functions_tpu import dpf as dpf_mod

    monkeypatch.setenv("DPF_TPU_EXPAND_LEVELS", "planes")
    monkeypatch.setenv("DPF_TPU_LEVEL_KERNEL", mode)
    captured = {}

    def fake_planes_fn(num_levels, **kwargs):
        captured.update(kwargs)
        return lambda *a: None

    monkeypatch.setattr(dpf_mod, "_expand_levels_planes_fn", fake_planes_fn)
    dpf_mod._expand_levels_fn(4, hash_leaves=True)
    assert captured["level_kernel"] is True
    assert captured.get("tail_kind") == tail_kind
    assert (captured["tail_req"] > 0) == has_tail
    assert captured["head_req"] == head_req


# ---------------------------------------------------------------------------
# Database staging
# ---------------------------------------------------------------------------


def test_bitrev_host_copy_dropped_after_device_staging():
    records = [RNG.bytes(8) for _ in range(300)]
    db = DenseDpfPirDatabase(records)
    host = db._host_words_bitrev()
    assert db._host_rev is not None
    dev = db._row_words(bitrev_blocks=True)
    assert db._host_rev is None  # dropped once the device copy exists
    np.testing.assert_array_equal(np.asarray(dev), host)
    # A later staging that needs the host copy rebuilds it.
    np.testing.assert_array_equal(db._host_words_bitrev(), host)


def test_streaming_chunks_cached_per_plan_key():
    records = [RNG.bytes(8) for _ in range(256)]
    db = DenseDpfPirDatabase(records)
    a = db.streaming_chunks(cut_levels=1, bitmajor=False)
    assert db.streaming_chunks(cut_levels=1, bitmajor=False) is a
    b = db.streaming_chunks(cut_levels=0, bitmajor=False)
    assert b is not a
    assert b.shape[0] == 1 and a.shape[0] == 2


def test_concurrent_staging_builds_once(monkeypatch):
    """Concurrent first requests must not stage the database twice (each
    staging is a full HBM copy)."""
    from distributed_point_functions_tpu.pir import dense_eval_planes_v2 as v2

    records = [RNG.bytes(8) for _ in range(512)]
    db = DenseDpfPirDatabase(records)
    calls = []
    orig = v2.streaming_block_permute_records

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setattr(v2, "streaming_block_permute_records", counting)
    out, errors = [], []

    def worker():
        try:
            out.append(db.streaming_chunks(cut_levels=2, bitmajor=False))
        except Exception as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(calls) == 1
    assert all(o is out[0] for o in out)
