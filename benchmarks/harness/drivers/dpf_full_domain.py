"""Full-domain DPF evaluation: a back-to-back stream of single-key
evaluations through `DistributedPointFunction.evaluate_next` on a fresh
evaluation context, cycling a pool of key pairs drawn from the seed.

Configuration keys (configs/<config>.json, `sizes`): `log_domain`,
`value_bits` (the integer value type).

Traffic keys (traffic/<mix>.json): `pool` key pairs. Each cycle visits
the pairs in a seeded order, both parties of a pair back to back. An
evaluation counts as done when its output is ready on the device.

After the window the last output of every pool key is read back, and
each pair's two shares must add up (mod 2^value_bits) to the point
function (reference.py). Betas are drawn from [2^32, 2^64), so a value
that loses its high word does not pass.

`control=True` evaluates keys of the next narrower value type the
program offers (IntType(value_bits / 2), betas reduced to it) and holds
them to the same reference.
"""

from __future__ import annotations

import time

import numpy as np

import reference
import seeding


def as_uint(out) -> np.ndarray:
    """One evaluation's output as unsigned integers of its value width:
    uint32[n, 2] limbs (low, high) become uint64[n]."""
    a = np.asarray(out)
    if a.ndim == 2 and a.shape[1] == 2 and a.dtype == np.uint32:
        return a[:, 0].astype(np.uint64) | (a[:, 1].astype(np.uint64) << 32)
    return a.reshape(a.shape[0], -1)[:, 0] if a.ndim == 2 else a


class Driver:
    def __init__(self, config, traffic, seed, log, control=False):
        self.sizes = config["sizes"]
        self.traffic = traffic
        self.seed = int(seed)
        self.log = log
        self.control = control

    def setup(self):
        from distributed_point_functions_tpu.dpf import (
            DistributedPointFunction,
            DpfParameters,
        )
        from distributed_point_functions_tpu.value_types import IntType

        self.log_domain = int(self.sizes["log_domain"])
        bits = int(self.sizes["value_bits"])
        self.key_bits = bits // 2 if self.control else bits
        self.dpf = DistributedPointFunction.create(
            DpfParameters(
                log_domain_size=self.log_domain,
                value_type=IntType(self.key_bits),
            )
        )
        rng = seeding.new_rng(self.seed, seeding.KEYS)
        size = int(self.traffic["pool"])
        self.alphas = [int(a) for a in rng.integers(0, 1 << self.log_domain, size)]
        self.betas = [
            int(b) for b in rng.integers(
                1 << 32, (1 << 64) - 1, size, dtype=np.uint64, endpoint=True
            )
        ]
        t0 = time.perf_counter()
        mask = (1 << self.key_bits) - 1
        with seeding.seeded_secrets(rng):
            self.pairs = [
                self.dpf.generate_keys(a, b & mask)
                for a, b in zip(self.alphas, self.betas)
            ]
        self.order_rng = seeding.new_rng(self.seed, seeding.SAMPLE)
        self.log(f"{size} key pairs in {time.perf_counter() - t0:.1f} s")

    def _evaluate(self, key):
        import jax

        out = self.dpf.evaluate_next([], self.dpf.create_evaluation_context(key))
        return jax.block_until_ready(out)

    def warm(self):
        for key in self.pairs[0]:
            self._evaluate(key)

    def window(self, seconds: float) -> dict:
        self.last = {}
        count = 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            for j in self.order_rng.permutation(len(self.pairs)):
                for party, key in enumerate(self.pairs[j]):
                    self.last[(int(j), party)] = self._evaluate(key)
                    count += 1
                if time.perf_counter() >= end:
                    break
        elapsed = time.perf_counter() - t0
        return {
            "seconds": seconds,
            "elapsed_s": elapsed,
            "attempted": count,
            "failed": 0,
            "evaluations": count,
            "leaves": 1 << self.log_domain,
            "log_domain": self.log_domain,
            "value_bits": int(self.sizes["value_bits"]),
        }

    def check(self, record: dict) -> list:
        wrong = unchecked = 0
        bits = self.key_bits
        for j, (alpha, beta) in enumerate(zip(self.alphas, self.betas)):
            if (j, 0) not in self.last or (j, 1) not in self.last:
                unchecked += 1
                continue
            s0, s1 = (as_uint(self.last[(j, p)]) for p in (0, 1))
            total = (s0 + s1) & np.asarray((1 << bits) - 1, dtype=s0.dtype)
            wrong += reference.wrong_leaves(
                total.astype(np.uint64), alpha, beta
            )
        return [("wrong_leaves", wrong, 0), ("unchecked_pairs", unchecked, 0)]

    def close(self):
        self.last = {}
