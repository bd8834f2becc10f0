"""YCSB core workload A as this benchmark runs it: the record's key and
value, the key that owns a record, and the draw of operations.

The data's shape is the source's (github.com/brianfrankcooper/YCSB,
`workloads/workloada` and `core/CoreWorkload.java`): a record is
`fieldcount` 10 fields of `fieldlength` 100 bytes, its key is "user" and
a hash of its number, an operation is a read or an update with
probability `readproportion` / `updateproportion`, and its record comes
from a zipfian distribution (constant 0.99) whose ranks are scattered
over the records by the FNV-1a hash of the rank. What is ours is in the
configuration's `assumed`: a record is held as ONE value of 1,000 bytes
(a `signedkv` write is one `key=value`), the zipfian is drawn over
`recordcount` items by the inverse of its exact cumulative weights (YCSB's
scrambled generator draws over 10^10 items by Gray's approximation), and
record i has its own Ed25519 key.

`reference/ycsb_ref.py` computes the same things again, on its own, and
a run is held to it.
"""

from __future__ import annotations

import bisect
import hashlib
import itertools
import random

from harness.chain import derive

FIELDS, FIELD_BYTES = 10, 100
FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3


def fnv1a64(n: int) -> int:
    """YCSB's `Utils.fnvhash64`: FNV-1a over the number's 8 octets, low
    octet first."""
    h = FNV_OFFSET
    for _ in range(8):
        h = ((h ^ (n & 0xFF)) * FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
        n >>= 8
    return h


def record_key(record: int) -> bytes:
    return b"user%d" % fnv1a64(record)


def record_value(seed: int, record: int, version: int) -> bytes:
    """The record's ten fields at `version` (0: as loaded; i + 1: as
    operation i wrote it), 100 printable bytes each, as one value."""
    out = []
    for f in range(FIELDS):
        stem = b"perfbench/%d/value/%d/%d/%d/" % (seed, record, version, f)
        out.append((hashlib.sha256(stem + b"0").hexdigest()
                    + hashlib.sha256(stem + b"1").hexdigest())[:FIELD_BYTES])
    return "".join(out).encode()


def record_secret(seed: int, record: int) -> bytes:
    return derive(seed, "record", record)


def loader_secret(seed: int) -> bytes:
    return derive(seed, "loader")


def make_keypair():
    """keypair(secret) -> (pubkey, sign): OpenSSL when the machine has it
    (a public key in 30 us, not 450), else the program's host code."""
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
    except ImportError:
        from tendermint_tpu.crypto import ed25519 as ed

        return lambda s: (ed.public_key(s), lambda msg: ed.sign(s, msg))

    def keypair(secret: bytes):
        key = Ed25519PrivateKey.from_private_bytes(secret)
        return key.public_key().public_bytes_raw(), key.sign

    return keypair


class Zipfian:
    """Ranks 0..n-1 with weight 1 / (rank + 1)^theta, drawn by the inverse
    of the cumulative weights; `record(u)` scatters the rank over the
    records as YCSB's ScrambledZipfianGenerator does."""

    def __init__(self, n: int, theta: float):
        self.n = n
        self.cum = list(itertools.accumulate(
            1.0 / (k + 1) ** theta for k in range(n)))

    def record(self, u: float) -> int:
        rank = min(bisect.bisect_right(self.cum, u * self.cum[-1]), self.n - 1)
        return fnv1a64(rank) % self.n


def stream(seed: int, label: str) -> random.Random:
    return random.Random(int.from_bytes(derive(seed, label)[:8], "big"))


def draw_operations(seed: int, n: int, recordcount: int, read_share: float,
                    theta: float) -> list[tuple[str, int]]:
    """The first n operations of the seed: ("read" | "update", record).
    Two uniforms an operation, in this order: the kind, the record."""
    rng, zipf = stream(seed, "ycsb-ops"), Zipfian(recordcount, theta)
    out = []
    for _ in range(n):
        kind = "read" if rng.random() < read_share else "update"
        out.append((kind, zipf.record(rng.random())))
    return out


def draw_history(seed: int, recordcount: int, theta: float):
    """The records updated before the run, without end: the same
    zipfian, a stream of its own."""
    rng, zipf = stream(seed, "ycsb-history"), Zipfian(recordcount, theta)
    while True:
        yield zipf.record(rng.random())
