"""End-to-end hierarchical-evaluation benchmark on synthetic sparse
histograms — the equivalent of the reference's experiments harness
(`experiments/synthetic_data_benchmarks.cc:45-308`).

One DPF key for a random nonzero index is expanded hierarchically: at each
configured hierarchy level only the prefixes that are "live" in the
synthetic workload (plus the expansion-factor cap) are evaluated, mirroring
the heavy-hitters evaluation strategy of `experiments/README.md:18-24`.

Flags mirror the reference's absl flags:
  --input PATH               CSV whose first column holds the nonzero
                             indices (`synthetic_data_benchmarks.cc:121-144`;
                             the reference's checked-in CSVs are git-lfs
                             stubs, so --distribution synthesizes equivalent
                             workloads when no file is given)
  --distribution {uniform,powerlaw10,powerlaw50}
  --log_domain_size N        total domain bits (default 32)
  --log_num_nonzeros N       synthetic workload size (default 14)
  --levels_to_evaluate a,b,c hierarchy levels (default auto: every 2 bits
                             from log_num_nonzeros+1)
  --max_expansion_factor F   cap on per-level expansion (default 4)
  --only_nonzeros            batched single-point evaluation at the nonzero
                             indices instead of hierarchical evaluation
                             (`synthetic_data_benchmarks.cc:55-58,299-302`)
  --num_iterations N
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root


def synthesize_nonzeros(distribution: str, log_domain_size: int, n: int,
                        rng: np.random.Generator) -> np.ndarray:
    """Random nonzero indices with the reference's workload shapes
    (`experiments/README.md:35-48`): uniform, or power-law with 90% of mass
    in the first 10%/50% of the domain.

    Returns uint64[m, 2] (hi, lo) limb pairs, sorted and deduplicated —
    domains up to 2^128 (`experiments/README.md:72-108`) exceed any numpy
    integer dtype. For log_domain_size <= 64, hi is identically 0.
    """
    def draw(bits, k):
        hi_bits = max(0, bits - 64)
        lo_bits = min(bits, 64)
        hi = (
            _rand_bits(rng, hi_bits, k)
            if hi_bits
            else np.zeros(k, dtype=np.uint64)
        )
        return hi, _rand_bits(rng, lo_bits, k)

    if distribution == "uniform":
        hi, lo = draw(log_domain_size, n)
    else:
        head = rng.random(n) < 0.9
        frac = 0.1 if distribution == "powerlaw10" else 0.5
        if log_domain_size <= 64:
            # Exact head bound (the reference's 10%/50% of the domain);
            # frac < 1 keeps the bound within uint64 even at lds = 64.
            bound = max(1, int((1 << log_domain_size) * frac))
            h_lo = rng.integers(0, bound, n, dtype=np.uint64)
            h_hi = np.zeros(n, dtype=np.uint64)
        else:
            # Beyond numpy's integer range: power-of-two head bound
            # (domain/8 ~ 12.5% for powerlaw10, domain/2 exact for 50%).
            frac_bits = log_domain_size - (3 if frac == 0.1 else 1)
            h_hi, h_lo = draw(frac_bits, n)
        t_hi, t_lo = draw(log_domain_size, n)
        hi = np.where(head, h_hi, t_hi)
        lo = np.where(head, h_lo, t_lo)
    return np.unique(np.stack([hi, lo], axis=1), axis=0)


def _rand_bits(rng: np.random.Generator, bits: int, k: int) -> np.ndarray:
    """k random uint64 values of `bits` (<= 64) random low bits."""
    if bits <= 0:
        return np.zeros(k, dtype=np.uint64)
    vals = rng.integers(0, 1 << min(bits, 63), k, dtype=np.uint64)
    if bits == 64:
        vals = (vals << np.uint64(1)) | rng.integers(
            0, 2, k, dtype=np.uint64
        )
    return vals


def _pairs_to_ints(pairs: np.ndarray) -> list:
    """uint64[m, 2] (hi, lo) -> python ints (arbitrary precision)."""
    return [(int(h) << 64) | int(l) for h, l in pairs]


def _unique_prefixes(pairs: np.ndarray, shift: int) -> list:
    """Distinct `x >> shift` over (hi, lo) pairs, as python ints."""
    hi = pairs[:, 0]
    lo = pairs[:, 1]
    if shift >= 64:
        p = np.unique(hi >> np.uint64(shift - 64))
        return [int(x) for x in p]
    if shift == 0:
        u = np.unique(pairs, axis=0)
        return _pairs_to_ints(u)
    u = np.unique(
        np.stack([hi, lo >> np.uint64(shift)], axis=1), axis=0
    )
    return [(int(h) << (64 - shift)) | int(l) for h, l in u]


def read_unique_values_from_file(path: str) -> list:
    """Unique integers in the first CSV column (sorted python ints —
    values may exceed 64 bits), like the reference's
    `ReadUniqueValuesFromFile` (`synthetic_data_benchmarks.cc:121-144`)."""
    values = set()
    with open(path) as f:
        for line_number, line in enumerate(f):
            fields = [x.strip() for x in line.split(",") if x.strip()]
            if not fields:
                raise ValueError(f"Line {line_number} is empty")
            values.add(int(fields[0]))
    return sorted(values)


def main():
    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    import os

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    parser = argparse.ArgumentParser()
    parser.add_argument("--input", default="",
                        help="CSV of nonzero indices (first column)")
    parser.add_argument("--distribution", default="powerlaw10",
                        choices=["uniform", "powerlaw10", "powerlaw50"])
    parser.add_argument("--log_domain_size", type=int, default=32)
    parser.add_argument("--log_num_nonzeros", type=int, default=14)
    parser.add_argument("--levels_to_evaluate", default="")
    parser.add_argument("--max_expansion_factor", type=float, default=4.0)
    parser.add_argument("--only_nonzeros", action="store_true",
                        help="batched point eval at the nonzeros instead of "
                        "hierarchical evaluation (requires --input or "
                        "--distribution synthesis)")
    parser.add_argument("--num_iterations", type=int, default=1)
    args = parser.parse_args()

    import jax

    from distributed_point_functions_tpu.dpf import (
        DistributedPointFunction,
        DpfParameters,
    )
    from distributed_point_functions_tpu.value_types import IntType

    lds = args.log_domain_size
    if args.levels_to_evaluate:
        levels = [int(x) for x in args.levels_to_evaluate.split(",")]
    else:
        levels = list(range(args.log_num_nonzeros + 1, lds, 2)) + [lds]
    assert levels[-1] == lds, "last level must be the full domain"

    rng = np.random.default_rng(42)
    if args.input:
        values = read_unique_values_from_file(args.input)
        if not values:
            raise ValueError(f"--input {args.input} contains no values")
        if values[-1] >= (1 << lds):
            raise ValueError(
                f"nonzero {values[-1]} out of range for domain 2^{lds}"
            )
        nonzeros = np.array(
            [[v >> 64, v & ((1 << 64) - 1)] for v in values],
            dtype=np.uint64,
        )
    else:
        nonzeros = synthesize_nonzeros(
            args.distribution, lds, 1 << args.log_num_nonzeros, rng
        )

    params = [
        DpfParameters(log_domain_size=l, value_type=IntType(32))
        for l in levels
    ]
    dpf = DistributedPointFunction.create_incremental(params)
    mid = nonzeros[len(nonzeros) // 2]
    alpha = (int(mid[0]) << 64) | int(mid[1])
    k0, _ = dpf.generate_keys_incremental(alpha, [1] * len(levels))

    max_prefixes = int(args.max_expansion_factor * len(nonzeros))

    def one_iteration():
        ctx = dpf.create_evaluation_context(k0)
        total_evaluated = 0
        prefixes: list = []
        for i, level_bits in enumerate(levels):
            out = dpf.evaluate_until(i, prefixes, ctx)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            size = int(np.asarray(out).shape[0])
            total_evaluated += size
            if i + 1 < len(levels):
                # Keep the live prefixes of the workload at this level
                # (the server knows which buckets are nonzero), capped at
                # the expansion factor like the reference harness.
                live = _unique_prefixes(nonzeros, lds - level_bits)
                prefixes = live[:max_prefixes]
        return total_evaluated

    if args.only_nonzeros:
        # Batched single-point evaluation at the nonzero indices
        # (`RunBatchedSinglePointEvaluation`,
        # `synthetic_data_benchmarks.cc:299-302`).
        points = _pairs_to_ints(nonzeros)
        last_level = len(levels) - 1

        def one_iteration():
            out = dpf.evaluate_at(k0, last_level, points)
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            return len(points)

    total = one_iteration()  # warmup + size probe
    t0 = time.perf_counter()
    for _ in range(args.num_iterations):
        one_iteration()
    elapsed = (time.perf_counter() - t0) / args.num_iterations

    print(
        json.dumps(
            {
                "benchmark": (
                    "synthetic_only_nonzeros"
                    if args.only_nonzeros
                    else "synthetic_hierarchical_eval"
                ),
                "distribution": "file" if args.input else args.distribution,
                "log_domain_size": lds,
                "num_nonzeros": len(nonzeros),
                "levels": levels,
                "leaves_evaluated": total,
                "time_s": round(elapsed, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
