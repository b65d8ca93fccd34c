"""Everything a run draws comes from `--seed`.

`stream` gives each purpose (records, requests, arrivals, keys) its own
generator, so changing one draw never shifts another. Key generation in
the program draws its root seeds and one-time-pad seeds from the
`secrets` module; `seeded_secrets` points those two calls at a seeded
generator while the harness builds its inputs, and restores them after.
The X25519 hybrid encryption of the Helper's leg keeps its own
randomness (`os.urandom`): ciphertexts differ between runs of one seed,
their sizes and the work they cost do not.
"""

from __future__ import annotations

import contextlib
import secrets

import numpy as np

RECORDS, REQUESTS, ARRIVALS, KEYS, SAMPLE = range(5)


def new_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), stream])


@contextlib.contextmanager
def seeded_secrets(rng: np.random.Generator):
    saved = secrets.token_bytes, secrets.randbits

    def token_bytes(n=32):
        return rng.bytes(int(n))

    def randbits(k):
        return int.from_bytes(rng.bytes((k + 7) // 8), "little") >> (
            (-k) % 8
        )

    secrets.token_bytes, secrets.randbits = token_bytes, randbits
    try:
        yield
    finally:
        secrets.token_bytes, secrets.randbits = saved
