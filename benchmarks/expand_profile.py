"""Stage-by-stage timing of the plane-resident dense-PIR expansion.

The headline split shows ~8 ms of expansion per 64-query batch where the
bitsliced-AES gate count alone prices at ~0.7 ms of VPU time — this
script localizes the gap by timing each stage as its own jitted program:
the limb-space walk prologue, each [all-left; all-right] plane level at
its true width, the leaf value hash, and the exit transpose + bit-reversal
gather. Prints one JSON line per stage.

Run on the live chip after `capture_tpu.sh` (contention-free).
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root
import time

import numpy as np


def log(msg):
    print(f"[prof {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def slope(fn, iters=32, reps=3):
    def timed(n):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        for leaf in jax_tree_leaves(out):
            np.asarray(leaf)
        return time.perf_counter() - t0

    t1 = min(timed(1) for _ in range(reps))
    tn = min(timed(1 + iters) for _ in range(reps))
    return (tn - t1) / iters if tn > t1 else None


def jax_tree_leaves(x):
    import jax

    return jax.tree_util.tree_leaves(x)


def main():
    num_records = int(os.environ.get("BENCH_RECORDS", 1 << 20))
    nq = int(os.environ.get("BENCH_QUERIES", 64))

    import jax
    import jax.numpy as jnp

    from distributed_point_functions_tpu.serving.service import (
        install_compile_cache,
    )

    install_compile_cache()
    log(f"devices: {jax.devices()}")

    from distributed_point_functions_tpu import keys as fk
    from distributed_point_functions_tpu.ops.aes_bitslice import (
        limbs_to_planes,
        mmo_hash_planes,
        planes_to_limbs,
    )
    from distributed_point_functions_tpu.pir.client import DenseDpfPirClient
    from distributed_point_functions_tpu.pir.dense_eval import (
        _walk_zeros,
        stage_keys,
    )
    from distributed_point_functions_tpu.pir.dense_eval_planes import (
        bitrev_permutation,
        expand_level_planes,
        pack_key_bits,
        pack_key_planes,
        _tile_keys,
    )

    num_blocks = num_records // 128
    total_levels = max(0, math.ceil(math.log2(num_records)))
    expand_levels = min((num_blocks - 1).bit_length(), total_levels)
    walk_levels = total_levels - expand_levels

    rng = np.random.default_rng(5)
    client = DenseDpfPirClient.create(num_records, lambda pt, ci: pt)
    indices = [int(i) for i in rng.integers(0, num_records, nq)]
    keys0, _ = client._generate_key_pairs(indices)
    seeds0, control0, cw_seeds, cw_left, cw_right, last_vc = stage_keys(keys0)

    # Plane layout wants the key axis padded to a multiple of 32 (the
    # serving wrapper pads the same way).
    pad = (-seeds0.shape[0]) % 32
    if pad:
        seeds0 = jnp.pad(seeds0, ((0, pad), (0, 0)))
        control0 = jnp.pad(control0, ((0, pad),))
        cw_seeds = jnp.pad(cw_seeds, ((0, 0), (0, pad), (0, 0)))
        cw_left = jnp.pad(cw_left, ((0, 0), (0, pad)))
        cw_right = jnp.pad(cw_right, ((0, 0), (0, pad)))
        last_vc = jnp.pad(last_vc, ((0, pad), (0, 0)))

    results = {}

    def report(stage, per):
        ms = per * 1e3 if per is not None else None
        results[stage] = ms
        print(json.dumps({"stage": stage,
                          "ms": round(ms, 4) if ms else None}), flush=True)

    # Stage 1: limb-space walk prologue.
    walk = jax.jit(
        lambda s, c: _walk_zeros(
            s, c, cw_seeds[:walk_levels], cw_left[:walk_levels]
        )
    )
    seeds_w, control_w = jax.block_until_ready(walk(seeds0, control0))
    report("walk_prologue", slope(lambda: walk(seeds0, control0)))

    # Stage 2: entry transpose + packing.
    enter = jax.jit(
        lambda s, c: (limbs_to_planes(s), pack_key_bits(c.astype(jnp.uint32)))
    )
    state0, ctrl0 = jax.block_until_ready(enter(seeds_w, control_w))
    report("enter_planes", slope(lambda: enter(seeds_w, control_w)))

    # Stage 3: each expansion level at its true width — the XLA level
    # and (on TPU) the fused Pallas kernel side by side.
    from distributed_point_functions_tpu.ops.expand_planes_pallas import (
        expand_level_planes_pallas,
    )

    try_kernel = jax.default_backend() == "tpu"
    states = [(state0, ctrl0)]
    for i in range(expand_levels):
        lvl = walk_levels + i
        groups2 = 2 * states[-1][0].shape[-1]

        def level_fn(s, c, lvl=lvl, groups2=groups2):
            return expand_level_planes(
                s,
                c,
                _tile_keys(pack_key_planes(cw_seeds[lvl]), groups2),
                _tile_keys(pack_key_bits(cw_left[lvl]), groups2 // 2),
                _tile_keys(pack_key_bits(cw_right[lvl]), groups2 // 2),
            )

        level = jax.jit(level_fn)
        s_in, c_in = states[-1]
        states.append(jax.block_until_ready(level(s_in, c_in)))
        report(f"level_{i:02d}_groups{groups2}",
               slope(lambda l=level, s=s_in, c=c_in: l(s, c)))
        if try_kernel:
            def kernel_fn(s, c, lvl=lvl):
                return expand_level_planes_pallas(
                    s,
                    c,
                    pack_key_planes(cw_seeds[lvl]),
                    pack_key_bits(cw_left[lvl]),
                    pack_key_bits(cw_right[lvl]),
                )

            try:
                klevel = jax.jit(kernel_fn)
                jax.block_until_ready(klevel(s_in, c_in))
                report(
                    f"level_{i:02d}_groups{groups2}_kernel",
                    slope(lambda l=klevel, s=s_in, c=c_in: l(s, c)),
                )
            except Exception as e:  # noqa: BLE001
                log(f"kernel level {i} failed: {str(e).splitlines()[0]}")
                try_kernel = False

    state_f, ctrl_f = states[-1]

    # Stage 4: leaf value hash + correction.
    def value_fn(s, c):
        v = mmo_hash_planes(fk.RK_VALUE, s)
        vc_p = _tile_keys(pack_key_planes(last_vc), v.shape[-1])
        return v ^ (vc_p & c[None, None, :])

    value = jax.jit(value_fn)
    values = jax.block_until_ready(value(state_f, ctrl_f))
    report("value_hash", slope(lambda: value(state_f, ctrl_f)))

    # Stage 5: exit transpose + bitrev gather + truncation.
    nkp = seeds0.shape[0]
    perm = jnp.asarray(bitrev_permutation(expand_levels))

    def exit_fn(v):
        w = 1 << expand_levels
        out = planes_to_limbs(v).reshape(w, nkp, 4)
        out = jnp.moveaxis(out, 0, 1)
        return out[:, perm, :][:, :num_blocks, :]

    exitp = jax.jit(exit_fn)
    jax.block_until_ready(exitp(values))
    report("exit_planes_bitrev", slope(lambda: exitp(values)))

    # Same exit without the bit-reversal gather (what serving would pay
    # with a bitrev-staged database, `bitrev_leaves=True`): if the delta
    # is material, wiring the block-bitrev into database staging is the
    # next win; if not, the refactor isn't worth its complexity.
    def exit_nogather_fn(v):
        w = 1 << expand_levels
        out = planes_to_limbs(v).reshape(w, nkp, 4)
        return jnp.moveaxis(out, 0, 1)

    exitng = jax.jit(exit_nogather_fn)
    jax.block_until_ready(exitng(values))
    report("exit_planes_nogather", slope(lambda: exitng(values)))

    total = sum(
        v for k, v in results.items() if v and not k.endswith("_kernel")
    )
    print(json.dumps({"stage": "sum_of_stages_xla", "ms": round(total, 3)}),
          flush=True)


if __name__ == "__main__":
    main()
