"""Multiplexed prioritized connection (reference: p2p/connection.go).

One physical stream carries many logical channels. Outgoing messages are
chopped into <=1024-byte packets; the send scheduler picks the channel
with the least recently-sent-bytes/priority ratio (connection.go:364-399),
so high-priority channels (votes) preempt bulk ones (block parts) without
starving them. Send and recv are rate-limited with flowrate monitors;
ping/pong guards liveness.

Framing (ours, not go-wire): 1-byte packet type; msg packets are
[type=0x02][channel:1][eof:1][len:2 BE][payload]. Ping=0x01, Pong=0x03.

The stream below is a chain over a socket: a TCP socket or an in-memory
socketpair (tests), under the optional delay line, fuzz wrapper and
secret connection. Upstream gives a connection a send routine and a
receive routine; here no connection has a thread: the node's one I/O
loop (p2p/ioloop.py) reads and writes every connection's socket, and the
chain's layers are pushed through (`feed` on the way in, `seal` on the
way out) instead of being read and written.
"""

from __future__ import annotations

import math
import selectors
import socket
import struct
import threading
import time
from collections import deque
from dataclasses import dataclass

from tendermint_tpu.libs.flowrate import Monitor
from tendermint_tpu.libs.service import BaseService
from tendermint_tpu.p2p import ioloop

PACKET_TYPE_PING = 0x01
PACKET_TYPE_MSG = 0x02
PACKET_TYPE_PONG = 0x03

MAX_MSG_PACKET_PAYLOAD_SIZE = 1024  # connection.go:30
_MSG_HEADER = struct.Struct(">BBBH")  # type, channel, eof, payload len
_PING = bytes([PACKET_TYPE_PING])
_PONG = bytes([PACKET_TYPE_PONG])

# packets a pass takes from the channels and hands to the stream as ONE
# write: what queued up while the connection waited for its turn (a
# committee's HasVotes) costs one seal and one system call, not one a
# packet; past it the connection comes back after the loop's other work
BURST_PACKETS = 64
# the most one read takes from a socket
READ_BYTES = 1 << 16
_SEND_FLAGS = socket.MSG_DONTWAIT | getattr(socket, "MSG_NOSIGNAL", 0)


class FrameViolation(ValueError):
    """The peer broke the mconn framing contract: reassembly past a
    channel's recv ceiling, an unknown channel id, or an unknown packet
    type. Typed (round 18) so the switch's adversary accounting can
    classify it without sniffing message text."""


@dataclass
class MConnConfig:
    """Tunables (connection.go:28-36, config/config.go:245-246)."""

    send_rate: float = 512000.0  # bytes/s
    recv_rate: float = 512000.0
    flush_throttle: float = 0.1  # s
    ping_interval: float = 40.0  # s (pingTimeoutSeconds uses one knob)
    pong_timeout: float = 45.0
    send_queue_capacity: int = 1
    recv_buffer_capacity: int = 4096
    recv_message_capacity: int = 22020096  # 21MB — max block + slack
    send_timeout: float = 10.0  # Channel.sendBytes block limit


@dataclass(frozen=True)
class ChannelDescriptor:
    """Static channel registration (connection.go:510-546)."""

    id: int
    priority: int = 1
    send_queue_capacity: int = 1
    recv_buffer_capacity: int = 4096
    recv_message_capacity: int = 22020096


class _Channel:
    def __init__(self, desc: ChannelDescriptor, cfg: MConnConfig):
        self.desc = desc
        self.id = desc.id
        self.priority = max(desc.priority, 1)
        self.recently_sent = 0  # decayed by flush ticks (connection.go:544)
        self._queue: deque[bytes] = deque()
        self._queue_cap = desc.send_queue_capacity
        self._mtx = threading.Lock()
        self._not_full = threading.Condition(self._mtx)
        # read without the lock by the loop's scheduler: one consumer
        self._sending: bytes | None = None
        self._sent_off = 0
        self._recving = bytearray()
        self._recv_cap = desc.recv_message_capacity

    # -- send side ---------------------------------------------------------

    def send_bytes(self, msg: bytes, timeout: float) -> bool:
        """Queue a message; block up to `timeout` if the queue is full."""
        deadline = time.monotonic() + timeout
        with self._not_full:
            while len(self._queue) >= self._queue_cap:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._not_full.wait(left)
            self._queue.append(msg)
            return True

    def push_bytes(self, msg: bytes) -> None:
        """Queue a message past the queue's cap: a send made on the I/O
        loop, the thread that frees the room (what a receive callback
        sends is bounded by what it received)."""
        with self._mtx:
            self._queue.append(msg)

    def try_send_bytes(self, msg: bytes) -> bool:
        with self._mtx:
            if len(self._queue) >= self._queue_cap:
                return False
            self._queue.append(msg)
            return True

    def is_send_pending(self) -> bool:
        with self._mtx:
            return self._sending is not None or bool(self._queue)

    def send_queue_size(self) -> int:
        with self._mtx:
            return len(self._queue) + (1 if self._sending is not None else 0)

    def next_packet(self) -> bytes | None:
        """Pop the next <=1024B packet frame for this channel, or None."""
        with self._not_full:
            if self._sending is None:
                if not self._queue:
                    return None
                self._sending = self._queue.popleft()
                self._sent_off = 0
                self._not_full.notify()
            chunk = self._sending[self._sent_off : self._sent_off + MAX_MSG_PACKET_PAYLOAD_SIZE]
            self._sent_off += len(chunk)
            eof = 1 if self._sent_off >= len(self._sending) else 0
            if eof:
                self._sending = None
                self._sent_off = 0
            frame = _MSG_HEADER.pack(PACKET_TYPE_MSG, self.id, eof, len(chunk)) + chunk
            self.recently_sent += len(frame)
            return frame

    # -- recv side ---------------------------------------------------------

    def recv_packet(self, payload: bytes, eof: bool) -> bytes | None:
        """Reassemble; returns the full message when eof (connection.go:661-677)."""
        if len(self._recving) + len(payload) > self._recv_cap:
            raise FrameViolation(
                f"channel {self.id:#x} message exceeds {self._recv_cap} bytes"
            )
        self._recving += payload
        if eof:
            msg = bytes(self._recving)
            self._recving = bytearray()
            return msg
        return None


class MConnection(BaseService):
    """on_receive(channel_id, msg_bytes) and on_error(exc) run on the I/O
    loop; on_error fires once, on the first fatal stream error. `loop`:
    the loop that serves it (the switch's), default `ioloop.shared_loop()`.
    Starting it starts no thread."""

    def __init__(
        self,
        stream,
        channel_descs: list[ChannelDescriptor],
        on_receive,
        on_error,
        config: MConnConfig | None = None,
        name: str = "mconn",
        loop: ioloop.IOLoop | None = None,
    ):
        super().__init__(name=name)
        self.stream = stream
        self.config = config or MConnConfig()
        self.on_receive = on_receive
        self.on_error = on_error
        self.channels: dict[int, _Channel] = {
            d.id: _Channel(d, self.config) for d in channel_descs
        }
        self._chans = tuple(self.channels.values())
        self.send_monitor = Monitor()
        self.recv_monitor = Monitor()
        self._loop = loop if loop is not None else ioloop.shared_loop()
        self._errored = False
        # per-peer instrumentation (round 15): armed by set_peer_label
        # once the handshake knows who the peer is; None = uninstrumented
        # (pre-handshake traffic, raw harness mconns)
        self._pm = None
        self.last_recv = time.monotonic()
        self._last_pong = time.monotonic()
        # the loop's side; touched on the loop's thread only
        self._io_sock = None
        self._io_dead = False
        self._io_due = math.inf
        self._attached = False
        self._closed = False
        self._feeds: tuple = ()     # the chain's read path, bottom up
        self._seals: tuple = ()     # its write path, top down: (seal, stall)
        self._stalls: tuple = ()    # its held-back reads
        self._rbuf = bytearray()    # plaintext not yet a whole packet
        self._out = bytearray()     # sealed bytes the socket has not taken
        self._pong_pending = False
        self._next_ping = math.inf
        self._send_after = 0.0      # rate limit / stall: no write before
        self._recv_after = 0.0      # rate limit / stall: no read before
        self._held: deque = deque()  # (retry, give_up, deadline): hold_reads

    def set_peer_label(self, peer_id: str, registry=None) -> None:
        """Arm the p2p_peer_* families for this connection. `registry`
        scopes the series (the switch passes the node registry so two
        in-process nodes keep separate counters); default process-wide."""
        from tendermint_tpu.p2p.telemetry import PeerConnMetrics

        self._pm = PeerConnMetrics(peer_id, list(self.channels), registry)

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        layers, obj = [], self.stream
        while obj is not None and getattr(obj, "sock", None) is None:
            layers.append(obj)
            obj = getattr(obj, "stream", None)
        if obj is None:
            raise ConnectionError("an MConnection needs a stream over a socket")
        self._feeds = tuple(x.feed for x in reversed(layers) if hasattr(x, "feed"))
        self._seals = tuple((x.seal, getattr(x, "stall", None))
                            for x in layers if hasattr(x, "seal"))
        self._stalls = tuple(x.stall for x in layers if hasattr(x, "stall"))
        self._io_sock = obj.sock
        # the switch's admission timeout is still armed here (it clears it
        # once add_peer returns): the loop's reads and writes never wait
        # (MSG_DONTWAIT), but with a timeout Python polls the socket first
        self._io_sock.settimeout(None)
        self._loop.add(self)

    def on_stop(self) -> None:
        self._loop.remove(self)

    def _io_close_stream(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self.stream.close()
        except Exception:  # noqa: BLE001 — closing is best effort
            pass

    def _io_attached(self, now: float) -> None:
        self._attached = True
        # the first ping leaves as the connection starts: a link's round
        # trip is known (telemetry's per-peer record, the reactor's relay
        # hold) before anything depends on it, not ping_interval later
        self._next_ping = now
        self._io_send(now)

    def _fatal(self, exc: Exception) -> None:
        if self._errored:
            return
        self._errored = True
        if self.is_running():
            cb = self.on_error
            if cb is not None:
                cb(exc)

    # -- public send API ---------------------------------------------------

    def send(self, ch_id: int, msg: bytes) -> bool:
        if not self.is_running():
            return False
        ch = self.channels.get(ch_id)
        if ch is None:
            return False
        loop = self._loop
        if loop.on_loop():
            # a receive callback: the loop is the thread that frees a
            # channel's room, so waiting for it would never end
            ch.push_bytes(msg)
            ok = True
        else:
            ok = ch.send_bytes(msg, self.config.send_timeout)
        if ok:
            loop.mark(self)
        self._note_send(ch, ok)
        return ok

    def try_send(self, ch_id: int, msg: bytes) -> bool:
        if not self.is_running():
            return False
        ch = self.channels.get(ch_id)
        if ch is None:
            return False
        ok = ch.try_send_bytes(msg)
        if ok:
            self._loop.mark(self)
        self._note_send(ch, ok)
        return ok

    def _note_send(self, ch: _Channel, ok: bool) -> None:
        pm = self._pm
        if pm is None:
            return
        if ok:
            pm.queue_sample(ch.id, ch.send_queue_size())
        else:
            pm.send_failure(ch.id)

    def rtt_s(self) -> float | None:
        """This link's smoothed ping round trip; None before a sample."""
        pm = self._pm
        return pm.rtt.value() if pm is not None else None

    def rtt_record(self) -> dict | None:
        """count / min / last / smoothed of this link's ping round trips
        (telemetry.PeerRtt); None for an uninstrumented connection."""
        pm = self._pm
        return pm.rtt.record() if pm is not None else None

    def can_send(self, ch_id: int) -> bool:
        ch = self.channels.get(ch_id)
        return ch is not None and ch.send_queue_size() < ch.desc.send_queue_capacity

    # -- send scheduler (on the loop) ------------------------------------------

    def _least_ratio_channel(self) -> _Channel | None:
        """Fair pick: min recentlySent/priority among channels with data
        (connection.go:364-399)."""
        best, best_ratio = None, None
        for ch in self._chans:
            if ch._sending is None and not ch._queue:
                continue
            ratio = ch.recently_sent / ch.priority
            if best_ratio is None or ratio < best_ratio:
                best, best_ratio = ch, ratio
        return best

    def _send_pending(self) -> bool:
        return self._pong_pending or any(
            ch._sending is not None or ch._queue for ch in self._chans)

    def _io_send(self, now: float) -> None:
        """Write what is due: a pong, a ping, a burst of packets."""
        if not self._attached:
            return
        if not self._out:
            self._pump(now)
            if self._out:
                self._flush()
        self._rearm(now)

    def _io_write(self) -> None:
        self._flush()
        if not self._out:
            self._pump(time.monotonic())
            if self._out:
                self._flush()
        self._rearm(time.monotonic())

    def _pump(self, now: float) -> None:
        """One pass of upstream's send routine: a pong, a ping, up to
        BURST_PACKETS packets by fairness, each its own write."""
        cfg = self.config
        if now < self._send_after:
            return
        not_before = self.send_monitor.not_before(cfg.send_rate)
        if not_before > now:
            self._send_after = not_before
            return
        writes = []
        if self._pong_pending:
            self._pong_pending = False
            writes.append(_PONG)
        pong_late = False
        if now >= self._next_ping:
            self._next_ping = now + cfg.ping_interval
            # stamped BEFORE the write: the round trip then holds
            # everything the ping met on its way out
            if self._pm is not None:
                self._pm.ping_sent()
            writes.append(_PING)
            pong_late = now - self._last_pong > cfg.ping_interval + cfg.pong_timeout
        burst = []
        for _ in range(BURST_PACKETS):
            ch = self._least_ratio_channel()
            if ch is None:
                break
            frame = ch.next_packet()
            if frame is None:
                break
            burst.append(frame)
        else:
            # a burst's worth is going out and more may be queued
            self._loop.mark(self)
        self._loop.frames_out += len(writes) + len(burst)
        if burst:
            writes.append(burst[0] if len(burst) == 1 else b"".join(burst))
        # decay fairness counters once a pass (connection.go:544)
        for ch in self._chans:
            ch.recently_sent = int(ch.recently_sent * 0.8)
        if writes:
            self._put(writes, now)
        if burst and self._pm is not None:
            for frame in burst:
                # frame layout: type, channel, eof (msg done)
                self._pm.sent_frame(frame[1], len(frame), bool(frame[2]))
        if pong_late:
            raise TimeoutError("pong timeout")

    def _put(self, writes: list[bytes], now: float) -> None:
        """The writes through the chain's write path into the unsent
        tail (a delayed link's line takes its bytes itself). A layer
        that stalls holds the next pass back by one draw a chunk it is
        handed: a frame, under a secret connection."""
        chunks = writes
        self.send_monitor.update(sum(map(len, writes)))
        for seal, stall in self._seals:
            if stall is not None:
                self._send_after = max(self._send_after,
                                       now + stall(len(chunks)))
            chunks = seal(chunks)
        for data in chunks:
            self._out += data

    def _flush(self) -> None:
        out, sock = self._out, self._io_sock
        while out:
            try:
                n = sock.send(out, _SEND_FLAGS)
            except (BlockingIOError, InterruptedError):
                return
            del out[:n]

    # -- recv (on the loop) ----------------------------------------------------

    def _io_read(self) -> None:
        now = time.monotonic()
        try:
            data = self._io_sock.recv(READ_BYTES, socket.MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            data = b""
        if not data:
            raise ConnectionError("stream closed")
        chunks = [data]
        for feed in self._feeds:
            chunks = feed(chunks)
        self.last_recv = now
        self._take(b"".join(chunks))
        self._recv_after = self.recv_monitor.not_before(self.config.recv_rate)
        # a stalling layer holds the next read back by one draw a frame
        # this read opened above it (each read, where nothing frames)
        for stall in self._stalls:
            self._recv_after = max(self._recv_after,
                                   now + stall(len(chunks)))
        self._rearm(now)

    def _take(self, data: bytes) -> None:
        """Plaintext in: every whole packet it completes is handled, in
        order; a partial one waits for more."""
        self._rbuf += data
        self._parse()

    def _parse(self) -> None:
        buf = self._rbuf
        n, off, nbytes, packets = len(buf), 0, 0, 0
        pm = self._pm
        try:
            with memoryview(buf) as view:
                while off < n and not self._held and not self._io_dead:
                    ptype = buf[off]
                    if ptype == PACKET_TYPE_MSG:
                        if n - off < _MSG_HEADER.size:
                            break
                        ch_id, eof = buf[off + 1], buf[off + 2]
                        plen = (buf[off + 3] << 8) | buf[off + 4]
                        end = off + _MSG_HEADER.size + plen
                        if end > n:
                            break
                        payload = bytes(view[off + _MSG_HEADER.size:end])
                        off = end
                        nbytes += 1 + plen
                        packets += 1
                        ch = self.channels.get(ch_id)
                        if ch is None:
                            raise FrameViolation(f"unknown channel {ch_id:#x}")
                        if pm is not None:
                            pm.recv_packet(ch_id, _MSG_HEADER.size + plen,
                                           bool(eof))
                        msg = ch.recv_packet(payload, bool(eof))
                        if msg is not None and self.on_receive is not None:
                            self.on_receive(ch_id, msg)
                    elif ptype == PACKET_TYPE_PING:
                        off += 1
                        nbytes += 1
                        packets += 1
                        self._pong_pending = True
                    elif ptype == PACKET_TYPE_PONG:
                        off += 1
                        nbytes += 1
                        packets += 1
                        self._last_pong = time.monotonic()
                        if pm is not None:
                            pm.pong_received()
                    else:
                        raise FrameViolation(f"unknown packet type {ptype:#x}")
        finally:
            del buf[:off]
            if nbytes:
                self.recv_monitor.update(nbytes)
            self._loop.frames_in += packets
        if self._pong_pending:
            self._loop.mark(self)

    def _io_hold(self, retry, give_up, deadline: float) -> None:
        self._held.append((retry, give_up, deadline))

    def _io_timer(self, now: float) -> None:
        held = self._held
        while held:
            retry, give_up, deadline = held[0]
            if not retry():
                if now < deadline:
                    break
                give_up()
            held.popleft()
        if not held and self._rbuf:
            self._parse()
        self._io_send(now)

    def _rearm(self, now: float) -> None:
        """Register for what the connection waits on; set its next timer."""
        if self._io_dead:
            return
        events = 0
        if not self._held and self._recv_after <= now:
            events |= selectors.EVENT_READ
        if self._out:
            events |= selectors.EVENT_WRITE
        self._loop.set_events(self, events)
        # a write waits for the socket (the tail) or for its instant: the
        # ping is due no sooner
        if self._out:
            due = math.inf
        elif self._send_after > now:
            due = max(self._next_ping, self._send_after)
            if self._send_pending():
                due = self._send_after
        else:
            due = self._next_ping
        if self._held:
            due = min(due, now + ioloop.HOLD_RETRY_S)
        if self._recv_after > now:
            due = min(due, self._recv_after)
        self._io_due = due

    def status(self) -> dict:
        st = {
            "send_rate": self.send_monitor.status().avg_rate,
            "recv_rate": self.recv_monitor.status().avg_rate,
            "channels": {
                f"{ch.id:#x}": ch.send_queue_size() for ch in self.channels.values()
            },
        }
        if self._pm is not None:
            st["rtt"] = self.rtt_record()
        return st
