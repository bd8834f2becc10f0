"""Seeded adversarial stream wrapper (reference: p2p/fuzz.go).

Audited for the round-18 adversarial tier: the reference's silent
read/write DROP mode (`prob_drop_rw`) predated the secure transport and
was broken against `SecretConnection` — a silently dropped write
desyncs the AEAD counter nonces, so every LATER frame fails
authentication and the wrapper poisons its own connection forever.
Nothing real was being simulated either: TCP never loses stream bytes
silently (loss is retransmit latency, which `prob_sleep` models, and
which the WAN profiles in ops/netfaults model properly).

The drop mode is therefore replaced by `prob_corrupt`: a seeded
single-byte XOR on outbound writes. Layered where PeerConfig puts this
wrapper — UNDER the SecretConnection — a corrupted write is ciphertext
tamper on the wire, which the remote AEAD flags loudly
(p2p_secretconn_auth_failures_total + peer dropped for cause). That
makes FuzzedStream the adversarial tier's FRAME-CORRUPTION peer: a
hostile-but-fluent peer built over it speaks the real protocol while a
seeded fraction of its frames arrive tampered (docs/netchaos.md,
docs/secure-p2p.md threat model).

Delay modes (`prob_sleep`, `max_delay`) are unchanged — reads are only
ever delayed, never dropped, since dropping reads would desync framing
on our own side. `prob_sleep` SLEEPS IN THE CALLER before the read or
write: it stalls the writer (head-of-line blocking, a cut in frames a
second), which is what a retransmit does to a TCP stream. It is not a
propagation delay. A link's propagation delay — a frame written at t
leaves at t + d, the writer does not wait, order holds — is the delay
line's (p2p/delay_line.py, `[p2p] test_link_region` /
`test_link_rtt_ms`), which sits at this same place in the chain.
"""

from __future__ import annotations

import random
import time


class FuzzedStream:
    def __init__(
        self,
        stream,
        prob_corrupt: float = 0.0,
        prob_sleep: float = 0.0,
        max_delay: float = 0.1,
        seed: int | None = None,
    ):
        self.stream = stream
        self.prob_corrupt = prob_corrupt
        self.prob_sleep = prob_sleep
        self.max_delay = max_delay
        self.corrupted_writes = 0  # observable by harnesses/tests
        self._rng = random.Random(seed)

    def _draw_sleep(self) -> float:
        if self._rng.random() < self.prob_sleep:
            return self._rng.random() * self.max_delay
        return 0.0

    def stall(self, n: int = 1) -> float:
        """Seconds `prob_sleep` holds back `n` reads or writes (0.0 most
        of the time), one draw each: `read` and `write` sleep it in the
        caller; a connection on the I/O loop holds its next read or
        write back by it instead, drawing once a frame it writes through
        this stream and once a frame it opens above it."""
        return sum(self._draw_sleep() for _ in range(n))

    def _maybe_sleep(self) -> None:
        delay = self._draw_sleep()
        if delay:
            time.sleep(delay)

    def _corrupt(self, data: bytes) -> bytes:
        if data and self._rng.random() < self.prob_corrupt:
            buf = bytearray(data)
            buf[self._rng.randrange(len(buf))] ^= 0xFF
            data = bytes(buf)
            self.corrupted_writes += 1
        return data

    def seal(self, chunks: list[bytes]) -> list[bytes]:
        """The write path of a connection on the I/O loop: what `write`
        would hand below for each of `chunks`, one corruption draw each
        (a chunk is one frame when a secret connection sits above)."""
        return [self._corrupt(data) for data in chunks]

    def read(self, n: int) -> bytes:
        # reads are only delayed: dropping them would desync framing
        self._maybe_sleep()
        return self.stream.read(n)

    def write(self, data: bytes) -> None:
        self._maybe_sleep()
        self.stream.write(self._corrupt(data))

    def close(self) -> None:
        self.stream.close()

    def remote_addr(self) -> str:
        inner = getattr(self.stream, "remote_addr", None)
        return inner() if inner else "fuzzed"
