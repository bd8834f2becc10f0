"""YCSB core workload A over `signedkv`, computed plainly: which
operation the seed draws (read or update, on which record), what a
record holds when loaded and after each update, and which values a read
may return. hashlib, random and a list; nothing of the program and
nothing of the harness's generator (`harness/ycsb.py`), which a run is
held to through this module.

The rules (the configuration's `assumed` says which are YCSB's):
- operation i consumes two uniforms of `random.Random(S)`, S the first
  8 octets (big-endian) of sha256("perfbench/<seed>/ycsb-ops"): the
  first is under `read_share` for a read; the second, times the sum of
  the weights 1/(k+1)^theta for k < recordcount, falls in the cumulative
  weight of one rank; the record is FNV-1a-64(rank) mod recordcount;
- the record's key is "user" + decimal FNV-1a-64(record number); its
  value at version v (0 as loaded, i+1 as written by operation i) is ten
  fields, field f the first 100 characters of the two hex digests
  sha256(stem+"0"), sha256(stem+"1"), stem =
  "perfbench/<seed>/value/<record>/<v>/<f>/".
"""

from __future__ import annotations

import hashlib
import random


def _fnv(number: int) -> int:
    h = 14695981039346656037
    for octet in number.to_bytes(8, "little"):
        h = ((h ^ octet) * 1099511628211) % (1 << 64)
    return h


def key_of(record: int) -> bytes:
    return ("user%d" % _fnv(record)).encode()


def value_of(seed: int, record: int, version: int) -> bytes:
    fields = []
    for f in range(10):
        stem = "perfbench/%d/value/%d/%d/%d/" % (seed, record, version, f)
        two = "".join(hashlib.sha256((stem + tail).encode()).hexdigest()
                      for tail in "01")
        fields.append(two[:100])
    return "".join(fields).encode()


def owner_key(seed: int, record: int) -> bytes:
    """The public key that owns the record: Ed25519 of the secret
    sha256("perfbench/<seed>/record/<record>")."""
    from . import ed25519_ref

    return ed25519_ref.public_key(
        hashlib.sha256(b"perfbench/%d/record/%d" % (seed, record)).digest())


def operations(seed: int, count: int, recordcount: int, read_share: float,
               theta: float) -> list[tuple[str, int]]:
    """[("read" | "update", record)] for operations 0..count-1."""
    start = hashlib.sha256(b"perfbench/%d/ycsb-ops" % seed).digest()[:8]
    rng = random.Random(int.from_bytes(start, "big"))
    upto, total = [], 0.0
    for k in range(recordcount):
        total += 1.0 / (k + 1) ** theta
        upto.append(total)
    out = []
    for _ in range(count):
        kind = "read" if rng.random() < read_share else "update"
        mark = rng.random() * total
        lo, hi = 0, recordcount - 1          # the first rank whose
        while lo < hi:                       # cumulative weight passes mark
            mid = (lo + hi) // 2
            if upto[mid] > mark:
                hi = mid
            else:
                lo = mid + 1
        out.append((kind, _fnv(lo) % recordcount))
    return out


class Store:
    """The records as loaded (version 0) and as the acknowledged updates
    left them. An update is (record, version, height, position in its
    block): applied in the chain's order, the last one wins."""

    def __init__(self, seed: int, recordcount: int):
        self.seed, self.recordcount = seed, recordcount
        self.updates: dict[int, list[tuple[int, int, int]]] = {}

    def acknowledge(self, record: int, version: int, height: int,
                    position: int) -> None:
        self.updates.setdefault(record, []).append((height, position, version))

    def history(self, record: int) -> list[tuple[int, int, int]]:
        """(height, position, version) of the record's values in the
        chain's order, the loaded one first."""
        return [(0, 0, 0)] + sorted(self.updates.get(record, []))

    def value(self, record: int, version: int) -> bytes:
        return value_of(self.seed, record, version)

    def final(self, record: int) -> bytes:
        return self.value(record, self.history(record)[-1][2])

    def at_height(self, record: int, height: int) -> bytes:
        """What the record holds once the block at `height` is applied."""
        held = [v for h, _p, v in self.history(record) if h <= height]
        return self.value(record, held[-1])


def versions_a_read_may_return(history, writes: dict, node: int,
                               sent: float, done: float) -> set[int]:
    """`history`: the record's (height, position, version) in chain
    order; `writes[version]` = (node that acknowledged it, when it was
    sent, when it was acknowledged) of every update. A read sent to
    `node` at `sent` and answered at `done` may return: the last version
    that THIS node had acknowledged before `sent` (or an earlier floor:
    the loaded value, where it had acknowledged none), and every later
    version of the chain's order whose update was sent before `done`.
    Nothing older than the floor: that is `fresh_read`."""
    floor = 0
    for at, (_h, _p, version) in enumerate(history):
        if version and writes[version][0] == node and writes[version][2] < sent:
            floor = at
    allowed = {history[floor][2]}
    for _h, _p, version in history[floor + 1:]:
        if writes[version][1] < done:
            allowed.add(version)
    return allowed
