"""The BASELINE.json named configurations, runnable end to end.

Runs the two large configs that bench.py's headline metric does not
cover, mirroring the discipline of the reference's flag-driven harness
(`experiments/synthetic_data_benchmarks.cc:45-61`):

* ``dense_big``  — batched dense PIR: 2^22 records x 1024 concurrent
  queries on one chip (BASELINE config 3).
* ``sparse_big`` — cuckoo-hashed sparse PIR over 2^24 string keys
  (BASELINE config 5): measures build and serving separately.

``--scale smoke`` shrinks both (2^16 records / 2^14 keys) so the full
path runs on CPU in CI; ``--scale full`` is the benchmark configuration
(needs a TPU and a few GB of host RAM for the build).

HBM budget at full scale (v5e, 16 GB): dense 2^22 x 256 B = 1 GB
row-major + 1 GB bit-major staged copy + 0.5 GB packed selections for
1024 queries; sparse ~ 0.7 GB across the two bucket databases. Both fit
without chunking; beyond ~2^25 x 256 B the database would need the
chunked-expansion path instead (SURVEY.md §5 long-context notes).

Each result prints as one JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root


def _emit(**kv):
    print(json.dumps(kv), flush=True)


def _slope(fn, iters=4, reps=2):
    """Per-call seconds via slope timing (see bench.py)."""
    def timed(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        np.asarray(out)
        return time.perf_counter() - t0

    t1 = min(timed(1) for _ in range(reps))
    tn = min(timed(1 + iters) for _ in range(reps))
    if tn <= t1:
        return None
    return (tn - t1) / iters


def bench_dense_big(scale: str):
    import jax

    from distributed_point_functions_tpu.ops.inner_product import (
        xor_inner_product,
        xor_inner_product_bitplane,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
        xor_inner_product_pallas2_staged,
        xor_inner_product_pallas_staged,
    )
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.dense_eval import (
        evaluate_selection_blocks,
        stage_keys,
    )

    if scale == "full":
        num_records, record_bytes, num_queries = 1 << 22, 256, 1024
    else:
        num_records, record_bytes, num_queries = 1 << 16, 64, 64

    rng = np.random.default_rng(11)
    num_words = record_bytes // 4
    db_host = rng.integers(
        0, 1 << 32, (num_records, num_words), dtype=np.uint32
    )
    t0 = time.perf_counter()
    on_tpu = jax.default_backend() == "tpu"
    ip_name = "jnp"
    if on_tpu:
        db = jax.block_until_ready(
            permute_db_bitmajor(jax.device_put(db_host))
        )
        # Same tier order as the serving path: v2 Pallas, v1 Pallas,
        # then the pure-jnp bit-plane MXU path (all consume the staged
        # layout).
        inner_product, ip_name = xor_inner_product_bitplane, "bitplane"
        for cand_name, cand in (
            ("pallas2", xor_inner_product_pallas2_staged),
            ("pallas", xor_inner_product_pallas_staged),
        ):
            try:
                jax.block_until_ready(
                    cand(db, np.zeros((8, db.shape[1], 4), np.uint32))
                )
                inner_product, ip_name = cand, cand_name
                break
            except Exception as e:  # noqa: BLE001
                print(f"# {cand_name} unavailable: {e}", flush=True)
    else:
        db = jax.device_put(db_host)
        inner_product = xor_inner_product
    stage_db_s = time.perf_counter() - t0

    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    indices = [int(i) for i in rng.integers(0, num_records, num_queries)]
    t0 = time.perf_counter()
    keys0, _ = client._generate_key_pairs(indices)
    keygen_s = time.perf_counter() - t0
    staged = stage_keys(keys0)

    num_blocks = num_records // 128
    total_levels = max(0, math.ceil(math.log2(num_records)))
    expand_levels = min((num_blocks - 1).bit_length(), total_levels)
    walk_levels = total_levels - expand_levels

    @jax.jit
    def step(s0, c0, cs, cl, cr, vc, dbx):
        sel = evaluate_selection_blocks(
            s0, c0, cs, cl, cr, vc,
            walk_levels=walk_levels,
            expand_levels=expand_levels,
            num_blocks=num_blocks,
        )
        return inner_product(dbx, sel)

    t0 = time.perf_counter()
    jax.block_until_ready(step(*staged, db))
    compile_s = time.perf_counter() - t0
    per_batch = _slope(lambda: step(*staged, db))
    _emit(
        benchmark=f"dense_pir_{num_records}x{record_bytes}B_{num_queries}q",
        queries_per_s=(
            round(num_queries / per_batch, 2) if per_batch else None
        ),
        per_batch_ms=round(per_batch * 1e3, 3) if per_batch else None,
        compile_s=round(compile_s, 1),
        stage_db_s=round(stage_db_s, 2),
        keygen_s=round(keygen_s, 2),
        backend=jax.default_backend(),
        inner_product=ip_name,
    )


def bench_sparse_big(scale: str):
    import jax

    from distributed_point_functions_tpu.pir.cuckoo_database import (
        CuckooHashedDpfPirDatabase,
    )
    from distributed_point_functions_tpu.pir.sparse_client import (
        CuckooHashingSparseDpfPirClient,
    )
    from distributed_point_functions_tpu.pir.sparse_server import (
        CuckooHashingSparseDpfPirServer,
    )

    num_keys = (1 << 24) if scale == "full" else (1 << 14)
    value_bytes = 16
    query_counts = [
        int(q)
        for q in os.environ.get("BENCH_SPARSE_QUERIES", "64,128").split(",")
    ]

    rng = np.random.default_rng(13)
    t0 = time.perf_counter()
    params = CuckooHashingSparseDpfPirServer.generate_params(
        num_keys, seed=b"0123456789abcdef"
    )
    builder = CuckooHashedDpfPirDatabase.Builder().set_params(params)
    for i in range(num_keys):
        builder.insert(
            (b"k%012d" % i, rng.integers(0, 256, value_bytes,
                                          dtype=np.uint8).tobytes())
        )
    db = builder.build()
    build_s = time.perf_counter() - t0

    server = CuckooHashingSparseDpfPirServer.create_plain(params, db)
    client = CuckooHashingSparseDpfPirClient.create_from_public_params(
        server.get_public_params().SerializeToString(), lambda pt, ci: pt
    )
    for num_queries in query_counts:
        queries = [b"k%012d" % int(i) for i in
                   rng.integers(0, num_keys, num_queries)]

        t0 = time.perf_counter()
        req0, _req1 = client.create_plain_requests(queries)
        resp = server.handle_request(req0)
        first_s = time.perf_counter() - t0
        assert len(resp.dpf_pir_response.masked_response) == (
            2 * num_queries * params.num_hash_functions
        )

        # handle_request blocks internally (the inner product is read
        # back to host bytes), so wall-clock per call is the honest
        # serving time.
        per_batch = _slope(lambda: server.handle_request(req0), iters=3)
        _emit(
            benchmark=f"sparse_pir_{num_keys}keys_{num_queries}q",
            queries_per_s=(
                round(num_queries / per_batch, 2) if per_batch else None
            ),
            per_batch_ms=round(per_batch * 1e3, 3) if per_batch else None,
            build_s=round(build_s, 1),
            first_request_s=round(first_s, 1),
            num_buckets=params.num_buckets,
            backend=jax.default_backend(),
        )


def main():
    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument(
        "--suite", default="dense_big,sparse_big",
        help="comma-separated: dense_big,sparse_big",
    )
    args = ap.parse_args()
    suites = {"dense_big": bench_dense_big, "sparse_big": bench_sparse_big}
    for name in args.suite.split(","):
        suites[name.strip()](args.scale)


if __name__ == "__main__":
    main()
