"""Hierarchical DPF evaluation: a back-to-back stream of single-key
evaluations of every hierarchy level, each on a fresh evaluation
context (`DistributedPointFunction.evaluate_next`), cycling a pool of
incremental key pairs drawn from the seed.

Configuration keys (configs/<config>.json, `sizes`): `log_domains` (one
per hierarchy level), `value_bits` (the integer value type of every
level), `nonzeros` (distinct uniform points of the last level's domain).

Traffic keys (traffic/<mix>.json): `pool` key pairs. Each cycle visits
the pairs in a seeded order, both parties of a pair back to back.

Set-up draws the nonzeros; the prefixes of level i are the distinct
prefixes of the nonzeros at level i-1 (sorted uint64 arrays, data and
not key work). Each pair's alpha is one of the nonzeros, so its prefix
is evaluated at every level, and each level has its own beta in
[1, 2^32). An evaluation is all levels of one key, done when its last
level's output is ready on the device; the window's `leaves` is the
number of outputs of one key's whole hierarchy.

After the window the last outputs of every pool key are read back. At
every level each pair's two shares must add up (mod 2^value_bits) to
the level's beta at alpha's prefix and to 0 at every other output
(`wrong_outputs`), and each party's share must equal the plain
reference's (reference_hier.py) at a seeded sample of outputs per level
(`reference_mismatches`).

`control=True` evaluates keys of a narrower value type the program
offers (`IntType(value_bits / 2)`, betas reduced to it) and holds their
sums to the configuration's betas.
"""

from __future__ import annotations

import time

import numpy as np

import reference_hier
import seeding

SAMPLE_PER_LEVEL = 4096
CHECK_SAMPLE = seeding.SAMPLE + 1  # the seeded stream of sampled outputs
COUNTERS = ("dpf.hier.evaluations", "dpf.hier.readback_bytes",
            "dpf.exit.gather_leaves")


def _nonzeros(rng: np.random.Generator, count: int, bits: int) -> np.ndarray:
    """`count` distinct uniform draws from [0, 2^bits), sorted."""
    drawn = np.zeros(0, dtype=np.uint64)
    while len(drawn) < count:
        more = rng.integers(0, 1 << bits, count + count // 8 + 16,
                            dtype=np.uint64, endpoint=False)
        drawn = np.unique(np.concatenate([drawn, more]))
    return np.sort(rng.permutation(drawn)[:count])


class Driver:
    def __init__(self, config, traffic, seed, log, control=False):
        self.sizes = config["sizes"]
        self.traffic = traffic
        self.seed = int(seed)
        self.log = log
        self.control = control

    def setup(self):
        from distributed_point_functions_tpu import dpf as dpf_mod
        from distributed_point_functions_tpu.value_types import IntType

        if not hasattr(dpf_mod, "DevicePartialEvaluations"):
            raise RuntimeError(
                "this program has no array-prefix hierarchical path: "
                "evaluate_until would map each of ~10^6 prefixes in Python"
            )
        self.log_domains = [int(x) for x in self.sizes["log_domains"]]
        self.bits = int(self.sizes["value_bits"])
        self.key_bits = self.bits // 2 if self.control else self.bits
        self.dpf = dpf_mod.DistributedPointFunction.create_incremental([
            dpf_mod.DpfParameters(log_domain_size=lds,
                                  value_type=IntType(self.key_bits))
            for lds in self.log_domains
        ])
        t0 = time.perf_counter()
        last = self.log_domains[-1]
        nonzeros = _nonzeros(seeding.new_rng(self.seed, seeding.RECORDS),
                             int(self.sizes["nonzeros"]), last)
        self.prefixes = [None] + [
            np.unique(nonzeros >> np.uint64(last - lds))
            for lds in self.log_domains[:-1]
        ]
        self.per_prefix = [1 << self.log_domains[0]] + [
            1 << (b - a)
            for a, b in zip(self.log_domains, self.log_domains[1:])
        ]
        self.outputs = [self.per_prefix[0]] + [
            len(p) * opp
            for p, opp in zip(self.prefixes[1:], self.per_prefix[1:])
        ]
        self.log(f"{len(nonzeros)} nonzeros; live prefixes by level "
                 f"{[len(p) for p in self.prefixes[1:]]}; "
                 f"{sum(self.outputs)} outputs a key")

        rng = seeding.new_rng(self.seed, seeding.KEYS)
        size = int(self.traffic["pool"])
        levels = len(self.log_domains)
        self.alphas = [int(a) for a in rng.choice(nonzeros, size)]
        self.betas = [
            [int(b) for b in rng.integers(1, 1 << 32, levels,
                                          dtype=np.uint64)]
            for _ in range(size)
        ]
        mask = (1 << self.key_bits) - 1
        with seeding.seeded_secrets(rng):
            self.pairs = [
                self.dpf.generate_keys_incremental(a, [b & mask for b in bs])
                for a, bs in zip(self.alphas, self.betas)
            ]
        self.order_rng = seeding.new_rng(self.seed, seeding.SAMPLE)
        self.log(f"data and {size} key pairs in "
                 f"{time.perf_counter() - t0:.1f} s")

    def _evaluate(self, key) -> list:
        import jax

        ctx = self.dpf.create_evaluation_context(key)
        outs = [self.dpf.evaluate_next([], ctx)]
        for prefixes in self.prefixes[1:]:
            outs.append(self.dpf.evaluate_next(prefixes, ctx))
        jax.block_until_ready(outs[-1])
        return outs

    def warm(self):
        for key in self.pairs[0]:
            self._evaluate(key)

    def counters(self) -> dict:
        from distributed_point_functions_tpu.observability import tracing

        counts = tracing.runtime_counters.export()
        return {name: counts.get(name, 0) for name in COUNTERS}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {name: after[name] - before.get(name, 0) for name in after}

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
            "leaves": sum(self.outputs),
            "log_domains": self.log_domains,
            "value_bits": self.bits,
        }

    def _indices(self, level: int) -> np.ndarray:
        """Domain index of every output row of a level, in output order."""
        opp = self.per_prefix[level]
        if level == 0:
            return np.arange(opp, dtype=np.uint64)
        return (self.prefixes[level][:, None] * np.uint64(opp)
                + np.arange(opp, dtype=np.uint64)).reshape(-1)

    def check(self, record: dict) -> list:
        wrong = unchecked = mismatches = 0
        mask = np.uint64((1 << self.bits) - 1)
        rng = seeding.new_rng(self.seed, CHECK_SAMPLE)
        last = self.log_domains[-1]
        for j, (alpha, betas) in enumerate(zip(self.alphas, self.betas)):
            if (j, 0) not in self.last or (j, 1) not in self.last:
                unchecked += 1
                continue
            shares = [[np.asarray(o)[:, 0].astype(np.uint64) for o in
                       self.last[(j, p)]] for p in (0, 1)]
            for level, lds in enumerate(self.log_domains):
                total = (shares[0][level] + shares[1][level]) & mask
                index = self._indices(level)
                hit = index == np.uint64(alpha >> (last - lds))
                want = np.where(hit, np.uint64(betas[level]), np.uint64(0))
                wrong += int(np.count_nonzero(total != want))
                rows = rng.choice(len(index), min(SAMPLE_PER_LEVEL,
                                                  len(index)),
                                  replace=False)
                rows = np.union1d(rows, np.flatnonzero(hit))
                for party in (0, 1):
                    plain = reference_hier.shares(
                        self.pairs[j][party], self.log_domains,
                        self.key_bits, level, index[rows],
                    )
                    mismatches += int(np.count_nonzero(
                        plain != shares[party][level][rows]
                    ))
        return [("wrong_outputs", wrong, 0),
                ("reference_mismatches", mismatches, 0),
                ("unchecked_pairs", unchecked, 0)]

    def close(self):
        self.last = {}
