"""Plain reference of `burst_writes` traffic: from the seed alone, the
burst's writes (key, value, signer), which of them are forged, the
verdict plain Ed25519 (`ed25519_ref`) gives a sample of them, and the
values the valid ones leave. Nothing of the program or the generator.

The burst, as the configuration's source runs it (a client hands over
one batch of signed transfers at once): write j sets `b<seed mod
1000003>-<j>` to `v<j>`, signed by signer j mod `signers` (signer k's
secret is sha256 of `perfbench/<seed>/signer/<k>`). Six pairs of writes
are forged, each pair four writes apart (consecutive in one node's queue
under round-robin over four nodes), with a valid write on either side of
each: the first of a pair carries a flipped signature bit, the second an
altered last payload byte.
"""

from __future__ import annotations

import hashlib
import random

from . import ed25519_ref, kv_ref

FORGED_PAIRS = 6


def secret(seed: int, signer: int) -> bytes:
    return hashlib.sha256(b"perfbench/%d/signer/%d" % (seed, signer)).digest()


def payload(seed: int, j: int) -> bytes:
    return b"b%d-%d=v%d" % (seed % 1000003, j, j)


def forged(seed: int, n: int) -> dict[int, str]:
    """position -> "signature" or "message"; none below 64 writes."""
    if n < 64:
        return {}
    rng = random.Random(seed ^ 0xB0257)
    taken: set[int] = set()
    out: dict[int, str] = {}
    while len(out) < 2 * FORGED_PAIRS:
        p = rng.randrange(1, n - 5)
        around = {p - 1, p, p + 1, p + 3, p + 4, p + 5}
        if around & taken:
            continue
        taken |= around
        out[p], out[p + 4] = "signature", "message"
    return out


class Burst:
    """The burst of one seed: n writes over `signers` keys."""

    def __init__(self, seed: int, n: int, signers: int):
        self.seed, self.n, self.signers = seed, n, signers
        self.forged = forged(seed, n)
        self._pubs: dict[int, bytes] = {}

    def pubkey(self, j: int) -> bytes:
        k = j % self.signers
        if k not in self._pubs:
            self._pubs[k] = ed25519_ref.public_key(secret(self.seed, k))
        return self._pubs[k]

    def valid(self) -> list[int]:
        return [j for j in range(self.n) if j not in self.forged]

    def tx_fits(self, j: int, tx: bytes) -> bool:
        """`tx` is write j as the burst has it: its signer's key, its
        payload (for a forged message, the payload altered as planned)."""
        want = bytearray(payload(self.seed, j))
        if self.forged.get(j) == "message":
            want[-1] ^= 0x01
        return tx[:32] == self.pubkey(j) \
            and tx[kv_ref.SIG_TX_OVERHEAD:] == bytes(want)

    def verdict(self, tx: bytes) -> bool:
        """Plain Ed25519's verdict on a tx of the burst."""
        return kv_ref.tx_valid(tx)

    def values(self) -> kv_ref.KVReference:
        """What the valid writes leave (each key written once)."""
        ref = kv_ref.KVReference()
        for j in self.valid():
            ref.apply_payload(payload(self.seed, j))
        return ref
