"""The p2p I/O loop (p2p/ioloop.py): one thread serves every connection's
reads and writes, and nothing it calls may block it."""

import hashlib
import queue
import socket
import threading
import time
from types import SimpleNamespace

import pytest

from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.crypto.chacha20poly1305 import ChaCha20Poly1305
from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
from tendermint_tpu.p2p.conn import ChannelDescriptor, MConnConfig, MConnection
from tendermint_tpu.p2p.fuzz import FuzzedStream
from tendermint_tpu.p2p.ioloop import IOLoop
from tendermint_tpu.p2p.secret_connection import (
    SecretConnection,
    SecretConnectionError,
)
from tendermint_tpu.p2p.stream import SocketStream, pipe_pair

CH = 0x20


def wait_until(cond, timeout=5.0, tick=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


@pytest.fixture
def loop():
    lp = IOLoop("p2p.io.test")
    yield lp
    lp.stop()


def _pair(loop, on_a=None, on_b=None, secret=False, cap=1, **cfg_kw):
    """Two started connections on `loop` over a socketpair; each end's
    received messages and errors are collected unless a callback is
    given."""
    a, b = pipe_pair()
    if secret:
        ka, kb = gen_priv_key_ed25519(), gen_priv_key_ed25519()
        out = {}
        t = threading.Thread(
            target=lambda: out.update(b=SecretConnection(b, kb)), daemon=True)
        t.start()
        a = SecretConnection(a, ka)
        t.join(10)
        b = out["b"]
    got = {"a": [], "b": [], "err_a": [], "err_b": []}
    descs = [ChannelDescriptor(id=CH, priority=5, send_queue_capacity=cap)]
    cfg = MConnConfig(**cfg_kw)
    ma = MConnection(a, descs, on_a or (lambda ch, m: got["a"].append(m)),
                     got["err_a"].append, cfg, loop=loop)
    mb = MConnection(b, descs, on_b or (lambda ch, m: got["b"].append(m)),
                     got["err_b"].append, cfg, loop=loop)
    ma.start()
    mb.start()
    return ma, mb, got


# -- one thread ----------------------------------------------------------------


def test_sixteen_connections_are_served_by_one_thread(loop, monkeypatch):
    """A node at 15 peers ran 30 connection threads; on the loop it runs
    one, whatever the count, and a connection starts none."""
    started = []
    real_start = threading.Thread.start

    def start(self):
        started.append(self.name)
        real_start(self)

    # the threads this test starts, not the process's count: other tests
    # of the same process may leave threads that end while it runs
    monkeypatch.setattr(threading.Thread, "start", start)
    pairs = [_pair(loop) for _ in range(8)]  # 16 connections
    assert wait_until(lambda: loop._thread is not None)
    assert started == ["p2p.io.test"]
    assert [t.name for t in threading.enumerate()].count("p2p.io.test") == 1
    assert not hasattr(MConnection, "_send_routine")
    assert not hasattr(MConnection, "_recv_routine")
    for i, (ma, mb, _) in enumerate(pairs):
        assert ma.send(CH, b"to b %d" % i)
        assert mb.send(CH, b"to a %d" % i)
    for i, (_, _, got) in enumerate(pairs):
        assert wait_until(lambda: got["b"] == [b"to b %d" % i]
                          and got["a"] == [b"to a %d" % i])
    # every connection's first ping, plus the 16 messages, each way
    assert loop.frames_in >= 32 and loop.frames_out >= 32
    assert loop.wakes >= 1
    assert set(loop.stats()) == {"p2p_io_wakes", "p2p_io_frames_in",
                                 "p2p_io_frames_out"}
    assert started == ["p2p.io.test"]
    for ma, mb, got in pairs:
        ma.stop()
        mb.stop()
        assert not got["err_a"] and not got["err_b"]


# -- frames split across reads -------------------------------------------------


def _bare_secret(key: bytes) -> SecretConnection:
    """A secret connection past its handshake, both directions keyed by
    `key` (the sealer and the opener of one test)."""
    sc = SecretConnection.__new__(SecretConnection)
    sc.stream = None
    sc._send_aead = sc._recv_aead = ChaCha20Poly1305(key)
    sc._send_nonce = sc._recv_nonce = 0
    sc._wmtx, sc._rmtx = threading.Lock(), threading.Lock()
    sc._recv_buf, sc._inbuf, sc._poisoned = b"", bytearray(), None
    return sc


def test_a_frame_split_at_every_offset_opens_whole():
    """The loop reads whatever the socket holds: a frame may end anywhere,
    inside its 2-byte length too. Every split gives back the plaintext
    whole, in order, and nothing early."""
    key = hashlib.sha256(b"split").digest()
    plain = [bytes(range(256)) * 6, b"short", b""]
    sealer = _bare_secret(key)
    frames = sealer.seal(plain)
    assert len(frames) == 4  # 1536 bytes are two frames; b"" is one
    wire = b"".join(frames)
    for i in range(len(wire) + 1):
        for j in (i, min(i + 1, len(wire))):
            rx = _bare_secret(key)
            out = (rx.feed([wire[:i]]) + rx.feed([wire[i:j]])
                   + rx.feed([wire[j:]]))
            assert out == [plain[0][:1024], plain[0][1024:]] + plain[1:], (i, j)
            assert not rx._inbuf
    # a partial frame opens nothing yet
    rx = _bare_secret(key)
    assert rx.feed([wire[:1]]) == [] and rx.feed([wire[1:30]]) == []


def test_a_packet_split_at_every_offset_is_handled_whole():
    """The same for the connection's own packets, across the plaintext a
    read opens."""
    packets = (bytes([0x02, CH, 0, 0x04, 0x00]) + b"a" * 1024
               + bytes([0x02, CH, 1, 0x00, 0x03]) + b"end"
               + bytes([0x02, CH, 1, 0x00, 0x00]))
    a, b = pipe_pair()
    for i in range(len(packets) + 1):
        got = []
        mc = MConnection(a, [ChannelDescriptor(id=CH)],
                         lambda ch, m: got.append(m), None)
        mc._take(packets[:i])
        mc._take(packets[i:])
        assert got == [b"a" * 1024 + b"end", b""], i
        assert not mc._rbuf
    a.close()
    b.close()


# -- one peer does not hold the others --------------------------------------------


def test_a_peer_that_stops_reading_does_not_stall_the_others(loop):
    """A blocking writer stuck on a peer that reads nothing held back that
    connection alone when it had a thread; on the loop the unsent tail
    waits for its socket and every other connection goes on."""
    deaf_end, deaf_peer = socket.socketpair()
    got_deaf_err = []
    deaf = MConnection(SocketStream(deaf_end),
                       [ChannelDescriptor(id=CH, send_queue_capacity=64)],
                       None, got_deaf_err.append,
                       MConnConfig(send_rate=0, recv_rate=0), loop=loop)
    deaf.start()
    ma, mb, got = _pair(loop, send_rate=0, recv_rate=0)
    blob = b"z" * 60_000
    while True:
        sent = deaf.try_send(CH, blob)
        if not sent and len(deaf._out) > 0:
            break
        if not sent:
            time.sleep(0.001)
    for i in range(20):
        assert ma.send(CH, b"a->b %d" % i)
        assert mb.send(CH, b"b->a %d" % i)
    assert wait_until(lambda: len(got["b"]) == 20 and len(got["a"]) == 20,
                      timeout=2.0)
    assert got["b"][-1] == b"a->b 19" and got["a"][-1] == b"b->a 19"
    assert deaf._out and not got_deaf_err
    for c in (deaf, ma, mb):
        c.stop()
    deaf_peer.close()


def test_a_bad_tag_poisons_that_connection_alone(loop):
    """Tampering on one secret connection ends it loudly (the error is
    typed, never EOF); its neighbour on the loop keeps talking."""
    ma, mb, got = _pair(loop, secret=True)
    mc, md, got2 = _pair(loop, secret=True)
    assert ma.send(CH, b"before")
    assert wait_until(lambda: got["b"] == [b"before"])
    # a frame of garbage straight onto the first pair's raw socket
    ma.stream.stream.write(b"\x00\x20" + b"\x00" * 32)
    assert wait_until(lambda: got["err_b"])
    assert isinstance(got["err_b"][0], SecretConnectionError)
    for i in range(5):
        assert mc.send(CH, b"still %d" % i)
        assert md.send(CH, b"back %d" % i)
    assert wait_until(lambda: len(got2["b"]) == 5 and len(got2["a"]) == 5)
    assert not got2["err_a"] and not got2["err_b"]
    for c in (ma, mb, mc, md):
        c.stop()


class _CountingFuzz(FuzzedStream):
    """The fuzz wrapper, counting its draws and the frames it is handed
    a write."""

    def __init__(self, stream):
        super().__init__(stream, seed=1)
        self.sleep_draws = self.corrupt_draws = self.most_frames = 0

    def _draw_sleep(self):
        self.sleep_draws += 1
        return super()._draw_sleep()

    def _corrupt(self, data):
        self.corrupt_draws += 1
        return super()._corrupt(data)

    def seal(self, chunks):
        self.most_frames = max(self.most_frames, len(chunks))
        return super().seal(chunks)


def test_the_fuzz_wrapper_draws_once_a_frame_each_way(loop):
    """Under a secret connection the fuzz wrapper was written to a frame
    at a time, and read from twice a frame, by the connection's threads.
    On the loop one pass hands it many frames at once; it still draws a
    corruption and a stall for each frame written, as the threads did,
    and a stall for each frame opened (the threads' two reads drew
    two)."""
    a, b = pipe_pair()
    fa, fb = _CountingFuzz(a), _CountingFuzz(b)
    out = {}
    t = threading.Thread(target=lambda: out.update(
        b=SecretConnection(fb, gen_priv_key_ed25519())), daemon=True)
    t.start()
    sa = SecretConnection(fa, gen_priv_key_ed25519())
    t.join(10)
    sb = out["b"]
    ends = [(fa, sa), (fb, sb)]
    # after the handshake, which wrote and read through the blocking path
    base = [(f.sleep_draws, f.corrupt_draws, s._send_nonce, s._recv_nonce)
            for f, s in ends]
    got = []
    descs = [ChannelDescriptor(id=CH, send_queue_capacity=64)]
    ma = MConnection(sa, descs, None, None, loop=loop)
    mb = MConnection(sb, descs, lambda ch, m: got.append(m), None, loop=loop)
    ma.start()
    mb.start()
    for i in range(40):
        assert ma.send(CH, b"%02d" % i + b"." * 3000)
    assert wait_until(lambda: len(got) == 40)
    ma.stop()
    mb.stop()
    assert wait_until(lambda: ma._closed and mb._closed)
    assert fa.most_frames > 1  # a pass sealed several frames at once
    for (f, s), (sleeps, corrupts, sent, opened) in zip(ends, base):
        written = s._send_nonce - sent
        assert f.corrupt_draws - corrupts == written
        assert f.sleep_draws - sleeps == written + s._recv_nonce - opened


# -- nothing a callback does blocks the loop ---------------------------------------


class _Consensus:
    """ConsensusState's hand-over to its receive routine, alone."""

    PEER_PUT_TIMEOUT = 0.4
    try_add_peer_message = ConsensusState.try_add_peer_message
    add_peer_message = ConsensusState.add_peer_message
    _enqueue_peer_msg = ConsensusState._enqueue_peer_msg

    def __init__(self):
        self.peer_msg_queue = queue.Queue(maxsize=1)
        self.drops = []

    def _note_peer_drop(self, mi):
        self.drops.append(mi.msg)


def _to_state(cons, msg, peer_id):
    """What ConsensusReactor.receive does with a vote, a part or a
    proposal from the peer `peer_id`."""
    ConsensusReactor._to_state(SimpleNamespace(con_s=cons), msg,
                               SimpleNamespace(id=lambda: peer_id))


def test_a_full_consensus_queue_parks_one_peer_not_the_loop(loop):
    cons = _Consensus()
    cons.peer_msg_queue.put("filler")
    mp_a, mp_b, _ = _pair(loop, on_b=lambda ch, m: _to_state(cons, m, "p"))
    mq_a, mq_b, got = _pair(loop)
    assert mp_a.send(CH, b"m1") and mp_a.send(CH, b"m2")
    time.sleep(0.05)
    t0 = time.monotonic()
    assert mq_a.send(CH, b"q1")
    assert wait_until(lambda: got["b"] == [b"q1"], timeout=0.3)
    assert time.monotonic() - t0 < cons.PEER_PUT_TIMEOUT
    assert not cons.drops
    # room within the time-out: nothing dropped, and in order
    assert cons.peer_msg_queue.get(timeout=1) == "filler"
    assert cons.peer_msg_queue.get(timeout=1).msg == b"m1"
    assert cons.peer_msg_queue.get(timeout=1).msg == b"m2"
    assert not cons.drops
    # no room: dropped and counted after the time-out, as before
    cons.peer_msg_queue.put("filler")
    assert mp_a.send(CH, b"m3")
    assert wait_until(lambda: cons.drops == [b"m3"], timeout=2)
    assert cons.peer_msg_queue.get_nowait() == "filler"
    # and the connection reads again
    assert mp_a.send(CH, b"m4")
    assert cons.peer_msg_queue.get(timeout=1).msg == b"m4"
    # off the loop there is no connection to hold back: dropped and
    # counted at once, with no wait
    cons.peer_msg_queue.put("filler")
    t0 = time.monotonic()
    _to_state(cons, b"off loop", "x")
    assert time.monotonic() - t0 < cons.PEER_PUT_TIMEOUT / 2
    assert cons.drops[-1] == b"off loop"
    for c in (mp_a, mp_b, mq_a, mq_b):
        c.stop()


def test_a_blocking_send_from_a_receive_callback_does_not_deadlock(loop):
    """A send made in a receive callback waited for room that only the
    loop frees: on the loop it queues past the channel's cap."""
    replies = []
    box = {}

    def answer(ch, msg):
        t0 = time.monotonic()
        ok = [box["mb"].send(CH, b"r%02d" % i + b"." * 3000) for i in range(20)]
        replies.append((all(ok), time.monotonic() - t0))

    ma, mb, got = _pair(loop, on_b=answer, cap=1)
    box["mb"] = mb
    mq_a, mq_b, got_q = _pair(loop)
    assert ma.send(CH, b"ask")
    assert wait_until(lambda: replies, timeout=3)
    assert replies[0][0] and replies[0][1] < 1.0, replies
    assert wait_until(lambda: len(got["a"]) == 20, timeout=3)
    assert [m[:3] for m in got["a"]] == [b"r%02d" % i for i in range(20)]
    assert mq_a.send(CH, b"after")
    assert wait_until(lambda: got_q["b"] == [b"after"], timeout=1)
    for c in (ma, mb, mq_a, mq_b):
        c.stop()


# -- timers ----------------------------------------------------------------------


def test_pong_timeout_still_fires(loop):
    """A peer that reads but never answers a ping is dropped at the next
    ping past ping_interval + pong_timeout, on the loop's clock."""
    end, mute = socket.socketpair()
    stop = threading.Event()

    def drain():
        mute.settimeout(0.05)
        while not stop.is_set():
            try:
                if not mute.recv(4096):
                    return
            except TimeoutError:
                pass
            except OSError:
                return

    threading.Thread(target=drain, daemon=True).start()
    errs = []
    mc = MConnection(SocketStream(end), [ChannelDescriptor(id=CH)], None,
                     errs.append, MConnConfig(ping_interval=0.05,
                                              pong_timeout=0.1), loop=loop)
    mc.start()
    try:
        assert wait_until(lambda: errs, timeout=3)
        assert isinstance(errs[0], TimeoutError)
        assert "pong timeout" in str(errs[0])
    finally:
        stop.set()
        mc.stop()
        mute.close()


def test_pings_answered_keep_a_quiet_link_alive(loop):
    ma, mb, got = _pair(loop, ping_interval=0.05, pong_timeout=0.1)
    time.sleep(0.5)
    assert not got["err_a"] and not got["err_b"]
    assert ma.rtt_s() is None  # uninstrumented; the round trips still ran
    assert ma._last_pong > time.monotonic() - 0.3
    ma.stop()
    mb.stop()


@pytest.mark.parametrize("side", ["send", "recv"])
def test_the_rate_limits_pace_the_loop_as_they_paced_the_threads(loop, side):
    """`send_rate` and `recv_rate` cap a connection's average rate; on the
    loop a connection over its cap waits for its instant instead of a
    thread sleeping. 400 KB at 200 KB/s, 0.3 s after the start, take
    about 1.7 s either way (the threads read the same)."""
    a, b = pipe_pair()
    got, done = [], threading.Event()

    def on_b(ch, m):
        got.append(len(m))
        if sum(got) >= 400_000:
            done.set()

    descs = [ChannelDescriptor(id=CH, send_queue_capacity=1000)]
    rates = {"send_rate": 200_000 if side == "send" else 0,
             "recv_rate": 200_000 if side == "recv" else 0}
    ma = MConnection(a, descs, None, None, MConnConfig(**rates), loop=loop)
    mb = MConnection(b, descs, on_b, None, MConnConfig(**rates), loop=loop)
    ma.start()
    mb.start()
    time.sleep(0.3)
    t0 = time.monotonic()
    for _ in range(100):
        assert ma.send(CH, b"x" * 4000)
    assert done.wait(10)
    assert 1.2 < time.monotonic() - t0 < 5.0
    ma.stop()
    mb.stop()


# -- the wire --------------------------------------------------------------------

# what the peer of one connection reads, handshake included, for the
# sequence below: recorded from the tree that ran two threads a
# connection, whose bytes on the wire this loop must not change
WIRE_LEN = 5798
WIRE_SHA256 = "0d1ba96fbb9fdc593787e5a63a895d49ddf408cbabb7883f0bd2e9be3fae2497"


def test_the_wire_bytes_are_those_of_the_threaded_connection(monkeypatch):
    from tendermint_tpu.crypto import x25519
    from tendermint_tpu.p2p import secret_connection as sc

    real = x25519.X25519PrivateKey

    class Fixed(real):
        __slots__ = ()

        @classmethod
        def generate(cls, backend=None):
            side = b"a" if threading.current_thread().name == "wire-a" else b"b"
            return real(hashlib.sha256(b"wire vector eph " + side).digest(),
                        backend=backend)

    class Recorded(SocketStream):
        def __init__(self, sock):
            super().__init__(sock)
            self.log = bytearray()

        def read(self, n):
            got = super().read(n)
            self.log += got
            return got

    monkeypatch.setattr(sc, "X25519PrivateKey", Fixed)
    sa, sb = socket.socketpair()
    out = {}
    t = threading.Thread(
        target=lambda: out.update(conn=SecretConnection(
            SocketStream(sa), gen_priv_key_ed25519(b"wire vector a"))),
        name="wire-a")
    t.start()
    rb = Recorded(sb)
    cb = SecretConnection(rb, gen_priv_key_ed25519(b"wire vector b"))
    t.join(10)

    def plain(n):
        buf = b""
        while len(buf) < n:
            got = cb.read(n - len(buf))
            assert got
            buf += got
        return buf

    descs = [ChannelDescriptor(id=0x20, priority=5, send_queue_capacity=4),
             ChannelDescriptor(id=0x21, priority=1, send_queue_capacity=4)]
    ma = MConnection(out["conn"], descs, None, lambda exc: None)
    ma.start()
    try:
        assert plain(1) == b"\x01"  # the first ping
        for ch, m in [(0x20, b"vote" * 40), (0x21, bytes(range(256)) * 12),
                      (0x20, b""), (0x21, b"x" * 1024), (0x20, b"y" * 1025)]:
            assert ma.send(ch, m)
            n = sum(5 + len(m[o:o + 1024])
                    for o in range(0, max(len(m), 1), 1024))
            got = plain(n)
            assert got[0] == 2 and got[1] == ch
    finally:
        ma.stop()
    wire = bytes(rb.log)
    assert len(wire) == WIRE_LEN
    assert hashlib.sha256(wire).hexdigest() == WIRE_SHA256
