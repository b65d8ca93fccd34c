"""Hardware A/B of the Pallas inner-product kernels (v1 vs v2 variants).

Times each candidate at the headline config (2^20 records x 256 B, 64
queries by default) on the live chip, verifying every candidate's output
bit-identity against the jnp XOR path on a small instance first and
against v1 on the full instance. Prints one JSON line per candidate to
stdout; run after `capture_tpu.sh` so the timings don't contend.

Reference semantics: `pir/internal/inner_product_hwy.cc:157-258`.
"""

from __future__ import annotations

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root
import time

import numpy as np


def log(msg):
    print(f"[ip_ab {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def slope(fn, iters=16, reps=3):
    def timed(n):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        np.asarray(out)
        return time.perf_counter() - t0

    t1 = min(timed(1) for _ in range(reps))
    tn = min(timed(1 + iters) for _ in range(reps))
    return (tn - t1) / iters if tn > t1 else None


def main():
    num_records = int(os.environ.get("BENCH_RECORDS", 1 << 20))
    record_bytes = int(os.environ.get("BENCH_RECORD_BYTES", 256))
    nq = int(os.environ.get("BENCH_QUERIES", 64))

    import jax

    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()

    from distributed_point_functions_tpu.ops.inner_product import (
        xor_inner_product,
    )
    from distributed_point_functions_tpu.ops.inner_product_pallas import (
        permute_db_bitmajor,
        xor_inner_product_pallas2_staged,
        xor_inner_product_pallas_staged,
    )

    log(f"devices: {jax.devices()}")
    rng = np.random.default_rng(11)
    num_words = record_bytes // 4

    # Every candidate pins ALL three tile knobs explicitly. (The r02
    # sweep's labels named defaults that were never passed — rows tagged
    # tg32_j8 actually ran tg128_j32, so two rows were the same config
    # measured twice, 2.7 vs 3.4 ms: that's the noise band. Hence
    # min-of-3 reps per candidate now, and honest labels.) The kernel
    # clamps tq = min(tile_queries, nq, vmem cap).
    candidates = {"v1": xor_inner_product_pallas_staged}
    seen_effective = set()
    for tg in (32, 64, 128):
        for jc in (8, 32):
            for tq in (64, 128):
                # The kernel clamps tq = min(tile_queries, nq, VMEM
                # cap): distinct requested tiles can collapse to one
                # effective config (the r02 duplicate-label bug) —
                # dedupe on the effective tuple (cap formula mirrors
                # _ip_pallas_staged_v2) so every row is a distinct
                # kernel.
                tq_cap = max(8, (2 << 20) // (32 * num_words * 4) // 8 * 8)
                eff_tq = min(tq, nq, tq_cap)
                eff = (tg, jc, eff_tq)
                if eff in seen_effective:
                    continue
                seen_effective.add(eff)
                candidates[f"v2_int8_tg{tg}_j{jc}_tq{eff_tq}"] = (
                    functools.partial(
                        xor_inner_product_pallas2_staged, int8=True,
                        tile_groups=tg, j_chunk=jc, tile_queries=tq,
                    )
                )
    candidates["v2_bf16_tg64_j32_tq64"] = functools.partial(
        xor_inner_product_pallas2_staged, int8=False, tile_groups=64,
        j_chunk=32, tile_queries=64,
    )

    # Small-instance verification vs the jnp XOR path.
    sdb = jax.device_put(
        rng.integers(0, 1 << 32, (4096, num_words), dtype=np.uint32)
    )
    ssel = jax.device_put(
        rng.integers(0, 1 << 32, (8, 32, 4), dtype=np.uint32)
    )
    sperm = permute_db_bitmajor(sdb)
    want = np.asarray(xor_inner_product(sdb, ssel))
    ok = {}
    for name, fn in candidates.items():
        try:
            got = np.asarray(fn(sperm, ssel))
            if not np.array_equal(got, want):
                raise RuntimeError("mismatch vs jnp")
            ok[name] = fn
            log(f"{name}: verified")
        except Exception as e:  # noqa: BLE001
            log(f"{name}: FAILED ({str(e).splitlines()[0]})")
            print(json.dumps({"candidate": name, "error":
                              str(e).splitlines()[0][:200]}), flush=True)

    # Full-instance staging and timing.
    db = jax.device_put(
        rng.integers(0, 1 << 32, (num_records, num_words), dtype=np.uint32)
    )
    db_perm = jax.block_until_ready(permute_db_bitmajor(db))
    nblocks = num_records // 128
    sel = jax.device_put(
        rng.integers(0, 1 << 32, (nq, nblocks, 4), dtype=np.uint32)
    )
    outs = {}
    for name, fn in ok.items():
        try:
            t0 = time.perf_counter()
            outs[name] = np.asarray(fn(db_perm, sel))
            compile_s = time.perf_counter() - t0
            per = slope(lambda f=fn: f(db_perm, sel))
            ms = per * 1e3 if per else None
            gbps = (num_records * num_words * 4 / per / 1e9) if per else None
            line = {
                "candidate": name,
                "ms": round(ms, 3) if ms else None,
                "gbps": round(gbps, 1) if gbps else None,
                "compile_s": round(compile_s, 1),
                "config": f"{num_records}x{record_bytes}B_{nq}q",
            }
            print(json.dumps(line), flush=True)
            log(line)
        except Exception as e:  # noqa: BLE001
            log(f"{name}: big-run FAILED ({str(e).splitlines()[0]})")
            print(json.dumps({"candidate": name, "error":
                              str(e).splitlines()[0][:200]}), flush=True)
    ref = outs.get("v1")
    if ref is not None:
        for name, got in outs.items():
            if not np.array_equal(got, ref):
                log(f"WARNING: {name} differs from v1 on the full instance")
                print(json.dumps({"candidate": name,
                                  "error": "full-instance mismatch vs v1"}),
                      flush=True)


if __name__ == "__main__":
    main()
