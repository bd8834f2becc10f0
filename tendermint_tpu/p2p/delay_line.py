"""Constant one-way link delay inside a node's own p2p stack.

A validator net across datacenters differs from one on loopback by one
thing a process can model exactly: a frame written at t leaves at
t + d, where d is half the round-trip time between the two regions.
`DelayedStream` sits where `FuzzedStream` sits in a peer's wrapper chain
(raw stream -> [delay line] -> [fuzz] -> secret connection), so the
secret connection's counters see bytes in the order they were sealed.
Its `write` stamps the chunk `due = now + d`, queues it and returns; the
node's ONE `DelayLine` thread writes every link's chunks to their
sockets at or after `due`, never before, FIFO per link. Reads are not
delayed: each direction of a link is delayed once, by its sender.

That is a propagation delay. `FuzzedStream`'s `prob_sleep` is not: it
sleeps in the caller, which stalls the writer (head-of-line blocking, a
cut in frames a second).

The delays are configuration of the normal node (`[p2p]`
`test_link_region`, `test_link_rtt_ms`: `LinkDelays.from_config`). Every
node of a net is given the same table of round-trip times between
regions and its own region; it announces `region=<name>` in
`NodeInfo.other`, and after the handshake each end sets
d = RTT(mine, theirs) / 2 on its side of the link. A peer that announces
no region, or one the table does not know, is refused. With nothing
configured the wrapper is absent from the chain and no thread starts.

There is one timer and it is native (native/src/delay_line.cc): a heap
of chunks and a thread that never takes the interpreter lock. A Python
timer thread needs that lock to wake and again after every send, in a
process whose 60-70 threads contend for it: 16 such nodes on 13 cores
read a mean lateness of 13-21 ms and a p95 of 64 ms beside one-way
delays of 12.5-156 ms, and a commit latency 300 ms higher (PERF.md,
PR 32): a net on that timer is another net. So there is no second
timer to fall back on: a node whose `[p2p]` configures delays refuses
to start where the library cannot be had, and a peer whose stream has
no socket is refused.

The timer thread writes with a blocking `send`: a peer that stops
reading until its socket buffer fills holds back every link of this
node (the I/O loop, which writes the undelayed links, keeps such a
link's unsent tail and goes on; the line cannot). That is a test
option's trade for one thread a node and not one a link.
"""

from __future__ import annotations

import ctypes
import os
import threading

REGION_KEY = "region="

# a link's counters as tm_delay_line_stats lays them out, before the
# lateness histogram
_STATS_HEAD = 5


def region_of(node_info) -> str | None:
    """The region a peer announced in `NodeInfo.other`, or None."""
    for entry in node_info.other:
        if isinstance(entry, str) and entry.startswith(REGION_KEY):
            return entry[len(REGION_KEY):]
    return None


class DelayLine:
    """The node's one timer over all of its delayed links: a native
    thread, started by the first link that is given a delay and ended by
    `stop()`. Raises RuntimeError where the library cannot be had."""

    def __init__(self):
        from tendermint_tpu import native

        api = native.delay_line_api()
        if api is None:
            raise RuntimeError(
                "the native delay line (native/src/delay_line.cc) cannot be "
                "built or loaded: make -C native")
        self._lib, self._pylib = api
        # upper edges, in seconds, of the lateness histogram every link
        # keeps (written less due); its last bucket is everything above
        edges = (ctypes.c_double * 64)()
        self.late_edges_s = tuple(edges[:self._lib.tm_delay_line_late_edges(edges, 64)])
        # guards the handle, so that no call is under way when stop()
        # frees it
        self._mtx = threading.Lock()
        self._handle = None
        self._stopped = False

    def attach(self, sock) -> int:
        """A link over a dup of `sock`: its id in the timer."""
        with self._mtx:
            if self._stopped:
                raise ConnectionError("delay line stopped")
            if self._handle is None:
                self._handle = self._lib.tm_delay_line_new()
            nid = self._lib.tm_delay_line_add_link(self._handle, sock.fileno())
        if nid < 0:
            raise ConnectionError(f"delayed link: {os.strerror(-nid)}")
        return nid

    def put(self, nid: int, delay_s: float, data: bytes) -> None:
        """Queue `data` for the link, due `delay_s` from now. The stamp
        and the push are one step under the timer's mutex, so two
        writers of one link keep their order."""
        with self._mtx:
            if self._handle is None:
                raise ConnectionError("delay line stopped")
            rc = self._pylib.tm_delay_line_put(
                self._handle, nid, delay_s, data, len(data))
        if rc < 0:
            raise ConnectionError(f"delayed link: {os.strerror(-rc)}")

    def close_link(self, nid: int) -> None:
        with self._mtx:
            if self._handle is not None:
                self._lib.tm_delay_line_close_link(self._handle, nid)

    def stats(self, nid: int) -> list[float] | None:
        out = (ctypes.c_double * (_STATS_HEAD + len(self.late_edges_s) + 1))()
        with self._mtx:
            if self._handle is None:
                return None
            self._pylib.tm_delay_line_stats(self._handle, nid, out)
        return list(out)

    def stop(self) -> None:
        with self._mtx:
            self._stopped = True
            handle, self._handle = self._handle, None
        if handle is not None:
            self._lib.tm_delay_line_free(handle)


class DelayedStream:
    """A stream whose writes leave `delay_s` after they were made. Until
    `set_delay` gives it a delay (the handshakes, which run before a
    peer's region is known) it writes straight through; from then on
    every write goes through the node's line."""

    def __init__(self, stream, line: DelayLine):
        self.stream = stream
        self._line = line
        self.delay_s = 0.0
        self.region = ""
        self._nid: int | None = None   # the link's id in the timer
        self._closed = False

    def set_delay(self, delay_s: float, region: str = "") -> None:
        self.delay_s = max(0.0, float(delay_s))
        self.region = region
        if self.delay_s > 0.0 and self._nid is None:
            sock = getattr(self.stream, "sock", None)
            if sock is None:
                raise ConnectionError("a delayed link needs a stream over a socket")
            self._nid = self._line.attach(sock)

    # -- the stream interface -------------------------------------------------

    def read(self, n: int) -> bytes:
        return self.stream.read(n)

    def write(self, data: bytes) -> None:
        if self._closed:
            raise ConnectionError("stream closed")
        if self._nid is None:
            self.stream.write(data)
        else:
            self._line.put(self._nid, self.delay_s, bytes(data))

    def seal(self, chunks: list[bytes]) -> list[bytes]:
        """The write path of a connection on the I/O loop: [] once the
        link has a delay (the line writes each chunk at its due instant,
        one line frame a chunk, as `write` puts them), else `chunks`,
        for the socket."""
        if self._closed:
            raise ConnectionError("stream closed")
        if self._nid is None:
            return chunks
        for data in chunks:
            self._line.put(self._nid, self.delay_s, bytes(data))
        return []

    def close(self) -> None:
        # what is queued is dropped, as a cut cable drops what is in it
        self._closed = True
        if self._nid is not None:
            self._line.close_link(self._nid)
        self.stream.close()

    def remote_addr(self) -> str:
        inner = getattr(self.stream, "remote_addr", None)
        return inner() if inner else "delayed"

    def stats(self) -> dict:
        """The link's record (docs/observability.md); a closed link
        keeps its counters until the line is stopped."""
        got = self._line.stats(self._nid) if self._nid is not None else None
        if got is None:
            got = [0.0] * (_STATS_HEAD + len(self._line.late_edges_s) + 1)
        frames, nbytes, queue_max, late_sum, late_max = got[:_STATS_HEAD]
        return {
            "region": self.region,
            "delay_s": self.delay_s,
            "frames": int(frames),
            "bytes": int(nbytes),
            "queue_max": int(queue_max),
            "late_sum_s": round(late_sum, 6),
            "late_max_s": round(late_max, 6),
            "late_hist": [int(x) for x in got[_STATS_HEAD:]],
            "late_edges_s": list(self._line.late_edges_s),
        }


class LinkDelays:
    """A node's region, the table of round-trip times between regions,
    and the node's one `DelayLine`."""

    def __init__(self, region: str, rtt_ms: dict[tuple[str, str], float]):
        self.region = region
        self.rtt_ms = dict(rtt_ms)
        # the library is loaded (built, in a fresh checkout) as the node
        # starts, not inside the first peer's handshake
        self.line = DelayLine()

    @classmethod
    def from_config(cls, p2p_cfg) -> "LinkDelays | None":
        """None when `[p2p]` configures no delay. A table that is not
        symmetric and complete over its regions (the diagonal included),
        or that lacks the node's own region, is a configuration error;
        so are delays where the native timer cannot be had."""
        region = (getattr(p2p_cfg, "test_link_region", "") or "").strip()
        table = (getattr(p2p_cfg, "test_link_rtt_ms", "") or "").strip()
        if not region and not table:
            return None
        if not region or not table:
            raise ValueError("p2p.test_link_region and p2p.test_link_rtt_ms "
                             "are set together or not at all")
        try:
            return cls(region, parse_rtt_table(table, must_hold=region))
        except RuntimeError as exc:
            raise ValueError(f"p2p.test_link_* is configured, and {exc}") from None

    def one_way_s(self, their_region: str | None) -> float:
        """Half the round trip between this node's region and a peer's.
        Raises ConnectionError for a peer that is no link of this net."""
        if not their_region:
            raise ConnectionError(
                "peer announced no region while link delays are configured")
        rtt = self.rtt_ms.get((self.region, their_region))
        if rtt is None:
            raise ConnectionError(
                f"peer's region {their_region!r} is not in the link table")
        return rtt / 2000.0


def parse_rtt_table(text: str, must_hold: str | None = None
                    ) -> dict[tuple[str, str], float]:
    """`a:b=ms,...` -> {(a, b): ms, (b, a): ms}. Every pair of the regions
    named, each region with itself included, has to be there."""
    out: dict[tuple[str, str], float] = {}
    for entry in text.split(","):
        entry = entry.strip()
        if not entry:
            continue
        try:
            pair, ms_text = entry.split("=")
            a, b = (s.strip() for s in pair.split(":"))
            ms = float(ms_text)
        except ValueError:
            raise ValueError(f"bad link table entry {entry!r}") from None
        if not a or not b or ms < 0:
            raise ValueError(f"bad link table entry {entry!r}")
        for key in ((a, b), (b, a)):
            if out.get(key, ms) != ms:
                raise ValueError(f"link table gives {a}:{b} two values")
            out[key] = ms
    regions = sorted({a for a, _ in out})
    missing = [f"{a}:{b}" for a in regions for b in regions if (a, b) not in out]
    if missing:
        raise ValueError(f"link table lacks {', '.join(missing[:6])}")
    if must_hold is not None and must_hold not in regions:
        raise ValueError(f"link table lacks this node's region {must_hold!r}")
    return out
