"""Benchmark runner mirroring the reference's benchmark binaries.

Suites (select with --suite, comma-separated; default all):

* ``dpf``           — full-domain expansion per value type and keygen /
                      batch point eval sweeps
                      (`dpf/distributed_point_function_benchmark.cc`)
* ``dcf``           — `batch_evaluate` sweep
                      (`dcf/distributed_comparison_function_benchmark.cc`)
* ``mic``           — batched MIC gate eval
                      (`dcf/fss_gates/multiple_interval_containment_benchmark.cc`)
* ``inner_product`` — database XOR inner product
                      (`pir/dense_dpf_pir_database_benchmark.cc`)
* ``int_mod_n``     — modular sampling throughput
                      (`dpf/int_mod_n_benchmark.cc`)

Each result prints as one JSON line. Scale knobs default small enough to run
on one chip in minutes; pass --big for the reference-sized sweeps.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

from benchmarks.common import run_timed  # noqa: E402


def bench_dpf(big: bool):
    import jax

    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.value_types import (
        IntType,
        TupleType,
        XorType,
    )

    log_domains = [12, 16, 20] if big else [12, 14]
    value_types = {
        "uint32": IntType(32),
        "uint64": IntType(64),
        "uint128": IntType(128),
        "xor128": XorType(128),
        "tuple_u32x2": TupleType([IntType(32), IntType(32)]),
    }
    for lds in log_domains:
        for name, vt in value_types.items():
            dpf = DistributedPointFunction.create(
                DpfParameters(log_domain_size=lds, value_type=vt)
            )
            k0, _ = dpf.generate_keys(3, vt.zero())
            leaves = 1 << lds

            def full_eval():
                ctx = dpf.create_evaluation_context(k0)
                out = dpf.evaluate_next([], ctx)
                jax.tree_util.tree_map(
                    lambda x: x.block_until_ready(), out
                )

            run_timed(
                f"dpf_full_domain_eval_2^{lds}_{name}",
                full_eval,
                items=leaves,
                unit="leaves/s",
            )

    # Key generation sweep (1..128 levels analog: bitwise hierarchies).
    for levels in [16, 64, 128] if big else [16, 32]:
        params = [
            DpfParameters(log_domain_size=i + 1, value_type=IntType(64))
            for i in range(levels)
        ]
        dpf = DistributedPointFunction.create_incremental(params)
        betas = [1] * levels

        run_timed(
            f"dpf_keygen_{levels}_levels",
            lambda: dpf.generate_keys_incremental(0, betas),
            iters=3,
        )

    # Batch point evaluation (400k points in the reference; scaled).
    n_points = 400_000 if big else 50_000
    lds = 32
    dpf = DistributedPointFunction.create(
        DpfParameters(log_domain_size=lds, value_type=IntType(64))
    )
    k0, _ = dpf.generate_keys(12345, 1)
    rng = np.random.default_rng(0)
    points = [int(x) for x in rng.integers(0, 1 << lds, n_points)]

    def point_eval():
        out = dpf.evaluate_at(k0, 0, points)
        out.block_until_ready()

    run_timed(
        f"dpf_batch_point_eval_{n_points}pts_2^{lds}",
        point_eval,
        items=n_points,
    )


def bench_dcf(big: bool):
    import jax

    from distributed_point_functions_tpu.dcf import (
        DistributedComparisonFunction,
    )
    from distributed_point_functions_tpu.value_types import IntType

    import time as _time

    # big includes the BASELINE.json config: 2^32 domain x 256 keys
    # (`dcf/distributed_comparison_function_benchmark.cc:31-74`).
    for lds in [32, 64] if big else [16, 32]:
        for batch in [64, 256, 1024] if big else [16, 256]:
            import random as _random

            dcf = DistributedComparisonFunction.create(lds, IntType(64))
            k0, k1 = dcf.generate_keys(3, 1)
            # Python randrange: domains beyond 2^63 overflow numpy int64.
            _r = _random.Random(0)
            xs = [_r.randrange(1 << lds) for _ in range(batch)]
            keys = [k0 if i % 2 == 0 else k1 for i in range(batch)]

            # Key staging is a one-time cost per batch; report it
            # separately so the eval number is pure device time.
            t0 = _time.perf_counter()
            staged = dcf.stage_keys(keys)
            jax.block_until_ready(staged.cw_seeds)
            stage_s = _time.perf_counter() - t0

            def batch_eval():
                out = dcf.batch_evaluate(None, xs, staged=staged)
                jax.tree_util.tree_map(
                    lambda x: x.block_until_ready(), out
                )

            run_timed(
                f"dcf_batch_eval_2^{lds}_batch{batch}",
                batch_eval,
                items=batch,
                label=f"stage_s={stage_s:.4f}",
            )


def bench_mic(big: bool):
    from distributed_point_functions_tpu.fss_gates import (
        Interval,
        MicParameters,
        MultipleIntervalContainmentGate,
    )

    log_group = 20
    num_intervals = 10 if big else 4
    num_keys = 16 if big else 4
    intervals = [
        Interval(i * 100, i * 100 + 50) for i in range(num_intervals)
    ]
    gate = MultipleIntervalContainmentGate.create(
        MicParameters(log_group, intervals)
    )
    k0, _ = gate.gen(7, [0] * num_intervals)
    xs = list(range(num_keys))

    run_timed(
        f"mic_batch_eval_{num_keys}keys_{num_intervals}intervals",
        lambda: gate.batch_eval([k0] * num_keys, xs),
        items=num_keys * num_intervals,
    )


def bench_inner_product(big: bool):
    import jax

    from distributed_point_functions_tpu.ops.inner_product import (
        xor_inner_product,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
        xor_inner_product_pallas_staged,
    )

    rng = np.random.default_rng(0)
    configs = (
        [(1 << 16, 80), (1 << 16, 256), (1 << 20, 80), (1 << 20, 256)]
        if big
        else [(1 << 16, 80), (1 << 16, 256)]
    )
    # The reference benches batch 1-2 (`dense_dpf_pir_database_benchmark
    # .cc:92-135`); the TPU design amortizes the database pass over a
    # whole query batch, so the 64-query point is the one that matters.
    for num_records, record_bytes in configs:
        num_padded = ((num_records + 127) // 128) * 128
        words = (record_bytes + 3) // 4
        db = jax.device_put(
            rng.integers(0, 1 << 32, (num_padded, words), dtype=np.uint32)
        )
        try:
            db_perm = jax.block_until_ready(permute_db_bitmajor(db))
        except Exception as e:  # noqa: BLE001
            db_perm = None
            print(f"# pallas staging skipped: {e}", flush=True)
        for nq in [1, 64] if big else [1]:
            sels = jax.device_put(
                rng.integers(
                    0, 1 << 32, (nq, num_padded // 128, 4), dtype=np.uint32
                )
            )

            run_timed(
                f"inner_product_jnp_{num_records}x{record_bytes}B_q{nq}",
                lambda: xor_inner_product(db, sels).block_until_ready(),
                items=num_records * nq,
            )
            if db_perm is None:
                continue
            try:
                run_timed(
                    f"inner_product_pallas_{num_records}x{record_bytes}B"
                    f"_q{nq}",
                    lambda: xor_inner_product_pallas_staged(
                        db_perm, sels
                    ).block_until_ready(),
                    items=num_records * nq,
                )
            except Exception as e:  # noqa: BLE001 - CPU backend has no Mosaic
                print(f"# pallas inner product skipped: {e}", flush=True)
        del db_perm


def bench_int_mod_n(big: bool):
    import jax

    from distributed_point_functions_tpu.value_types import IntModNType
    from distributed_point_functions_tpu.ops import limb

    vt = IntModNType(base_bits=32, modulus=1000003)
    n = (1 << 20) if big else (1 << 16)
    rng = np.random.default_rng(0)
    blocks = jax.device_put(
        rng.integers(0, 1 << 32, (n, 4), dtype=np.uint32)
    )

    def sample():
        q, r = limb.divmod_const(blocks, vt.modulus, 4)
        r.block_until_ready()

    run_timed(f"int_mod_n_sample_{n}", sample, items=n)


SUITES = {
    "dpf": bench_dpf,
    "dcf": bench_dcf,
    "mic": bench_mic,
    "inner_product": bench_inner_product,
    "int_mod_n": bench_int_mod_n,
}


def main():
    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    parser = argparse.ArgumentParser()
    parser.add_argument("--suite", default=",".join(SUITES))
    parser.add_argument("--big", action="store_true",
                        help="reference-sized sweeps")
    args = parser.parse_args()
    for name in args.suite.split(","):
        SUITES[name.strip()](args.big)


if __name__ == "__main__":
    main()
