"""Plain references, independent of the program under test.

Nothing here imports the program or takes anything it made: the records
and the one-time pads come from the harness's own seeded draws, the
keystream from the `cryptography` package's AES.

* Dense PIR: the answer to a query for index i is record i. A Leader /
  Helper answer is that record XORed with the AES-128-CTR keystream
  (key = the client's one-time-pad seed, initial counter 0, big-endian
  increment: OpenSSL `AES_ctr128_encrypt`) that the Helper masks its
  share with; a pair of plain answers XORs to the record.
* Full-domain DPF: the two parties' outputs add up (mod 2^bits) to the
  point function, beta at alpha and 0 at every other leaf.
"""

from __future__ import annotations

import numpy as np


def otp_keystream(seed: bytes, length: int) -> bytes:
    from cryptography.hazmat.primitives.ciphers import (
        Cipher,
        algorithms,
        modes,
    )

    enc = Cipher(algorithms.AES(bytes(seed)), modes.CTR(b"\0" * 16)).encryptor()
    return enc.update(b"\0" * length) + enc.finalize()


def masked_record(table: np.ndarray, index: int, seed: bytes) -> bytes:
    """What the Leader's combined answer must be for `index`."""
    record = table[index]
    pad = np.frombuffer(otp_keystream(seed, record.size), dtype=np.uint8)
    return (record ^ pad).tobytes()


def wrong_leaves(total: np.ndarray, alpha: int, beta: int) -> int:
    """Leaves of the summed shares that differ from the point function."""
    want_alpha = np.asarray(beta, dtype=total.dtype)
    wrong = int(np.count_nonzero(total))
    if total[alpha] != 0:
        wrong -= 1
    if total[alpha] != want_alpha:
        wrong += 1
    return wrong
