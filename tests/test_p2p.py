"""p2p layer tests (reference test models: p2p/switch_test.go,
connection_test.go, secret_connection_test.go, addrbook_test.go,
pex_reactor_test.go)."""

import threading
import time

import pytest

from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
from tendermint_tpu.p2p import (
    ChannelDescriptor,
    MConnection,
    NetAddress,
    NodeInfo,
    Reactor,
    Switch,
    connect2_switches,
    make_connected_switches,
)
from tendermint_tpu.p2p.addrbook import AddrBook
from tendermint_tpu.p2p.node_info import default_version
from tendermint_tpu.p2p.secret_connection import SecretConnection
from tendermint_tpu.p2p.stream import pipe_pair


def wait_until(cond, timeout=5.0, tick=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


# -- netaddress ---------------------------------------------------------------


def test_netaddress_parse_and_classify():
    a = NetAddress.from_string("127.0.0.1:26656")
    assert a.ip == "127.0.0.1" and a.port == 26656
    assert a.valid() and a.local() and not a.routable()
    assert NetAddress("8.8.8.8", 53).routable()
    assert not NetAddress("10.0.0.1", 80).routable()
    assert not NetAddress("notanip", 80).valid()
    with pytest.raises(ValueError):
        NetAddress.from_string("nocolon")
    assert NetAddress("8.8.8.8", 53).same_network(NetAddress("8.8.4.4", 99))


# -- secret connection --------------------------------------------------------


def test_secret_connection_roundtrip():
    a, b = pipe_pair()
    ka, kb = gen_priv_key_ed25519(), gen_priv_key_ed25519()
    out = {}

    def srv():
        out["conn"] = SecretConnection(b, kb)

    t = threading.Thread(target=srv, daemon=True)
    t.start()
    ca = SecretConnection(a, ka)
    t.join(5)
    cb = out["conn"]
    assert ca.remote_pubkey().raw == kb.pub_key().raw
    assert cb.remote_pubkey().raw == ka.pub_key().raw

    # large payload crosses frame boundaries
    payload = bytes(range(256)) * 20  # 5120 bytes > 1024 frame
    ca.write(payload)
    got = bytearray()
    while len(got) < len(payload):
        got += cb.read(4096)
    assert bytes(got) == payload
    # and the other direction
    cb.write(b"pong")
    assert ca.read(10) == b"pong"
    ca.close()


def test_secret_connection_tampering_detected():
    a, b = pipe_pair()
    ka, kb = gen_priv_key_ed25519(), gen_priv_key_ed25519()
    out = {}
    t = threading.Thread(
        target=lambda: out.update(conn=SecretConnection(b, kb)), daemon=True
    )
    t.start()
    ca = SecretConnection(a, ka)
    t.join(5)
    # corrupt a ciphertext frame on the raw stream underneath: tampering
    # must RAISE (round 12) — the old b"" return read as a graceful peer
    # hangup, hiding an active attack as EOF
    from tendermint_tpu.p2p.secret_connection import SecretConnectionError

    ca.stream.write(b"\x00\x20" + b"\x00" * 32)
    with pytest.raises(SecretConnectionError):
        out["conn"].read(10)
    # and the connection stays poisoned: every later read raises too
    with pytest.raises(SecretConnectionError):
        out["conn"].read(1)
    ca.close()


# -- mconnection --------------------------------------------------------------


def _mconn_pair(descs=None, **cfg_kw):
    from tendermint_tpu.p2p.conn import MConnConfig

    descs = descs or [ChannelDescriptor(id=0x01, priority=1)]
    a, b = pipe_pair()
    recv_a, recv_b = [], []
    err = []
    cfg = MConnConfig(**cfg_kw)
    ma = MConnection(a, descs, lambda ch, m: recv_a.append((ch, m)), lambda e: err.append(e), cfg)
    mb = MConnection(b, descs, lambda ch, m: recv_b.append((ch, m)), lambda e: err.append(e), cfg)
    ma.start()
    mb.start()
    return ma, mb, recv_a, recv_b, err


def test_mconnection_send_recv_multipacket():
    ma, mb, recv_a, recv_b, _ = _mconn_pair()
    msg = b"x" * 5000  # > 4 packets
    assert ma.send(0x01, msg)
    assert wait_until(lambda: recv_b and recv_b[0] == (0x01, msg))
    assert mb.send(0x01, b"reply")
    assert wait_until(lambda: recv_a and recv_a[0] == (0x01, b"reply"))
    ma.stop()
    mb.stop()


def test_mconnection_unknown_channel_refused():
    ma, mb, *_ = _mconn_pair()
    assert not ma.send(0x99, b"nope")
    assert not ma.try_send(0x99, b"nope")
    ma.stop()
    mb.stop()


def test_mconnection_ping_pong_keeps_alive():
    ma, mb, _, recv_b, err = _mconn_pair(ping_interval=0.05, pong_timeout=1.0)
    time.sleep(0.4)  # several ping cycles
    assert not err
    assert ma.send(0x01, b"still here")
    assert wait_until(lambda: recv_b)
    ma.stop()
    mb.stop()


def test_mconnection_peer_close_fires_on_error():
    ma, mb, _, _, err = _mconn_pair()
    mb.stream.close()
    assert wait_until(lambda: err)
    ma.stop()
    mb.stop()


def test_mconnection_priority_fairness():
    """High-priority channel data is not starved by a bulk channel."""
    descs = [
        ChannelDescriptor(id=0x01, priority=1, send_queue_capacity=100),
        ChannelDescriptor(id=0x02, priority=10, send_queue_capacity=100),
    ]
    ma, mb, _, recv_b, _ = _mconn_pair(descs)
    for _ in range(50):
        ma.try_send(0x01, b"bulk" * 256)
    ma.try_send(0x02, b"urgent")
    assert wait_until(
        lambda: any(ch == 0x02 for ch, _ in recv_b), timeout=10
    )
    ma.stop()
    mb.stop()


# -- switch -------------------------------------------------------------------


class EchoReactor(Reactor):
    """Records messages; replies on the same channel when asked."""

    def __init__(self, ch_id=0x05):
        self.ch_id = ch_id
        self.received = []
        self.peers = []

    def start(self):
        pass

    def stop(self):
        pass

    def get_channels(self):
        return [ChannelDescriptor(id=self.ch_id, priority=1, send_queue_capacity=32)]

    def add_peer(self, peer):
        self.peers.append(peer)

    def remove_peer(self, peer, reason):
        if peer in self.peers:
            self.peers.remove(peer)

    def receive(self, ch_id, peer, msg):
        self.received.append((peer.id(), msg))


def _make_net(n):
    reactors = []

    def init(i, sw):
        r = EchoReactor()
        reactors.append(r)
        sw.add_reactor("echo", r)
        return sw

    return make_connected_switches(n, init), reactors


def test_switch_broadcast_reaches_all_peers():
    sws, reactors = _make_net(3)
    try:
        sws[0].broadcast(0x05, b"fan-out")
        assert wait_until(lambda: len(reactors[1].received) == 1)
        assert wait_until(lambda: len(reactors[2].received) == 1)
        assert reactors[1].received[0][1] == b"fan-out"
    finally:
        for sw in sws:
            sw.stop()


def test_switch_refuses_self_and_duplicate_connections():
    sws, _ = _make_net(2)
    try:
        with pytest.raises(ConnectionError):
            connect2_switches(sws, 0, 1)  # duplicate peering
    finally:
        for sw in sws:
            sw.stop()


def test_switch_incompatible_network_rejected():
    def init_a(i, sw):
        sw.add_reactor("echo", EchoReactor())
        return sw

    sw_a, sw_b = Switch(), Switch()
    sw_a.add_reactor("echo", EchoReactor())
    sw_b.add_reactor("echo", EchoReactor())
    for sw, net in ((sw_a, "chain-A"), (sw_b, "chain-B")):
        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker="m",
                network=net,
                version=default_version("0.1.0"),
            )
        )
        sw.start()
    try:
        with pytest.raises(ConnectionError, match="network mismatch"):
            connect2_switches([sw_a, sw_b], 0, 1)
        assert sw_a.peers.size() == 0 and sw_b.peers.size() == 0
    finally:
        sw_a.stop()
        sw_b.stop()


def test_switch_stop_peer_for_error_removes_from_reactors():
    sws, reactors = _make_net(2)
    try:
        peer = sws[0].peers.list()[0]
        sws[0].stop_peer_for_error(peer, "test")
        assert sws[0].peers.size() == 0
        assert peer not in reactors[0].peers
        # remote side notices the close too
        assert wait_until(lambda: sws[1].peers.size() == 0)
    finally:
        for sw in sws:
            sw.stop()


def test_switch_tcp_listener_end_to_end():
    from tendermint_tpu.p2p.listener import Listener

    sw_a, sw_b = Switch(), Switch()
    ra, rb = EchoReactor(), EchoReactor()
    sw_a.add_reactor("echo", ra)
    sw_b.add_reactor("echo", rb)
    lst = Listener("127.0.0.1:0")
    sw_a.add_listener(lst)
    sw_a.start()
    sw_b.start()
    try:
        addr = lst.internal_address()
        peer = sw_b.dial_peer_with_address(NetAddress("127.0.0.1", addr.port))
        assert wait_until(lambda: sw_a.peers.size() == 1)
        peer.send(0x05, b"over tcp")
        assert wait_until(lambda: ra.received and ra.received[0][1] == b"over tcp")
    finally:
        sw_a.stop()
        sw_b.stop()


def test_an_outbound_connection_does_not_keep_a_listener_off_its_port():
    """PERF.md section 7, fault 9: a peer's dial took, as its LOCAL port,
    the port a node that booted a moment later was to serve RPC on, and
    that node died on `Address already in use`. The dial sets
    SO_REUSEADDR, so a listener (which sets it too) binds the port of a
    live outbound connection."""
    import socket

    from tendermint_tpu.p2p.listener import Listener
    from tendermint_tpu.p2p.switch import _dial

    lst = Listener("127.0.0.1:0")
    out = late = None
    try:
        out = _dial(("127.0.0.1", lst.internal_address().port), 3.0)
        assert out.getsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR)
        assert out.gettimeout() == 3.0
        late = Listener(f"127.0.0.1:{out.getsockname()[1]}")
        assert late.internal_address().port == out.getsockname()[1]
    finally:
        for s in (out, getattr(late, "sock", None), lst.sock):
            if s is not None:
                s.close()
    with pytest.raises(OSError):
        _dial(("127.0.0.1", lst.internal_address().port), 0.5)  # closed now


def test_inbound_ip_range_count_released_on_peer_removal():
    """Regression (round 12, caught by the real-TCP chaos tier): the
    inbound IP-range count is taken on the RAW socket stream, which peer
    admission wraps in a SecretConnection — removal must UNcount through
    the wrapper chain, or 16 inbound churn cycles from one /24 (any
    loopback testnet) permanently exhaust the accept budget."""
    from tendermint_tpu.p2p.listener import Listener

    sw_a, sw_b = Switch(), Switch()
    sw_a.add_reactor("echo", EchoReactor())
    sw_b.add_reactor("echo", EchoReactor())
    lst = Listener("127.0.0.1:0")
    sw_a.add_listener(lst)
    sw_a.start()
    sw_b.start()
    try:
        port = lst.internal_address().port
        for _ in range(3):
            sw_b.dial_peer_with_address(NetAddress("127.0.0.1", port))
            assert wait_until(lambda: sw_a.peers.size() == 1)
            assert sw_a.ip_ranges.count("127") == 1
            sw_a.stop_peer_for_error(sw_a.peers.list()[0], "churn")
            assert wait_until(lambda: sw_b.peers.size() == 0)
            # the count must drop with the peer — this leaked pre-round-12
            assert wait_until(lambda: sw_a.ip_ranges.count("127") == 0)
    finally:
        sw_a.stop()
        sw_b.stop()


# -- addrbook -----------------------------------------------------------------


def test_addrbook_add_pick_good(tmp_path):
    book = AddrBook(str(tmp_path / "addrbook.json"))
    src = NetAddress("1.2.3.4", 26656)
    for i in range(50):
        assert book.add_address(NetAddress(f"5.6.{i}.1", 26656), src) or True
    assert book.size() > 0
    picked = book.pick_address()
    assert picked is not None
    book.mark_good(picked)
    # non-routable rejected in strict mode
    assert not book.add_address(NetAddress("192.168.1.1", 26656), src)
    book.save()

    book2 = AddrBook(str(tmp_path / "addrbook.json"))
    assert book2.size() == book.size()
    assert any(str(picked) == str(ka.addr) and ka.is_old()
               for ka in book2._addrs.values())


def test_addrbook_selection_and_removal():
    book = AddrBook("", routability_strict=False)
    src = NetAddress("127.0.0.1", 1)
    for i in range(20):
        book.add_address(NetAddress("127.0.0.1", 1000 + i), src)
    sel = book.get_selection()
    assert 0 < len(sel) <= 20
    victim = sel[0]
    book.remove_address(victim)
    assert str(victim) not in book._addrs


# -- pex ----------------------------------------------------------------------


def test_pex_reactor_exchanges_addresses():
    from tendermint_tpu.p2p.pex import PEXReactor

    books = [AddrBook("", routability_strict=False) for _ in range(2)]
    books[0].add_address(NetAddress("127.0.0.1", 7771), NetAddress("127.0.0.1", 1))

    def init(i, sw):
        sw.add_reactor("pex", PEXReactor(books[i], ensure_peers_period=3600))
        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"n{i}",
                network="test",
                version=default_version("0.1.0"),
                listen_addr=f"127.0.0.1:{7000 + i}",
            )
        )
        return sw

    sws = make_connected_switches(2, init)
    try:
        # node1's inbound peer (node0... whichever side is inbound) requests
        # addrs; eventually node1 learns node0's known address
        assert wait_until(
            lambda: books[0].size() + books[1].size() >= 3, timeout=5
        )
    finally:
        for sw in sws:
            sw.stop()


# -- fuzz ---------------------------------------------------------------------


def test_fuzzed_stream_delays_but_delivers():
    from tendermint_tpu.p2p.fuzz import FuzzedStream

    a, b = pipe_pair()
    fa = FuzzedStream(a, prob_sleep=0.5, max_delay=0.01, seed=7)
    fa.write(b"through the fuzz")
    assert b.read(100) == b"through the fuzz"
    fa.close()


def test_switch_inbound_peer_cap():
    """Beyond max_num_peers, inbound connections are closed at accept
    (switch.go:462-467) — outbound/dialed peers are not affected."""
    import socket as _socket

    from tendermint_tpu.config.config import P2PConfig
    from tendermint_tpu.p2p.switch import Switch

    sw = Switch(config=P2PConfig(max_num_peers=1))

    class _FakePeer:
        def id(self):
            return "aa" * 20

        def key(self):
            return self.id()

    assert sw.peers.add(_FakePeer())  # at the cap
    a, b = _socket.socketpair()
    try:
        sw._accept_peer(a)
        b.settimeout(2)
        assert b.recv(1) == b""  # remote end sees an immediate close
    finally:
        for s in (a, b):
            try:
                s.close()
            except OSError:
                pass


def test_switch_ip_range_cap():
    """Inbound peers beyond the per-IP-range limit are closed at accept
    (ip_range_counter wiring)."""
    import socket as _socket

    from tendermint_tpu.p2p.ip_range_counter import IPRangeCounter
    from tendermint_tpu.p2p.switch import Switch

    sw = Switch()
    sw.ip_ranges = IPRangeCounter(limits=(1, 1, 1))
    assert sw.ip_ranges.try_add("127.0.0.1")  # range now full

    lst = _socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    cli = _socket.create_connection(lst.getsockname())
    srv, _ = lst.accept()
    try:
        sw._accept_peer(srv)
        cli.settimeout(2)
        assert cli.recv(1) == b""  # closed without handshake
    finally:
        for s in (cli, srv, lst):
            try:
                s.close()
            except OSError:
                pass


def test_addrbook_is_bad_and_eviction():
    import time as _time

    from tendermint_tpu.p2p import addrbook as ab

    book = AddrBook("", routability_strict=False)
    src = NetAddress("127.0.0.1", 1)
    addr = NetAddress("127.0.0.1", 2000)
    book.add_address(addr, src)
    ka = book._addrs[str(addr)]

    # fresh address: not bad
    assert not ka.is_bad()
    # repeated failures without a success -> bad (once past the
    # recent-attempt grace window)
    for _ in range(ab.MAX_FAILURES):
        book.mark_attempt(addr)
    assert not ka.is_bad()  # just tried: within RECENT_ATTEMPT grace
    ka.last_attempt -= ab.RECENT_ATTEMPT + 1
    assert ka.is_bad()
    # a success clears badness; old addresses are never bad
    book.mark_good(addr)
    assert ka.is_old() and not ka.is_bad()
    # staleness: not heard from in STALE_AFTER
    ka2 = ab.KnownAddress(NetAddress("127.0.0.1", 2001), src)
    ka2.added = _time.time() - ab.STALE_AFTER - 1
    assert ka2.is_bad()

    # mark_bad removes outright (ref MarkBad)
    book.mark_bad(addr)
    assert str(addr) not in book._addrs


def test_addrbook_pick_skips_bad():
    from tendermint_tpu.p2p import addrbook as ab

    book = AddrBook("", routability_strict=False)
    src = NetAddress("127.0.0.1", 1)
    good = NetAddress("127.0.0.1", 3000)
    bad = NetAddress("127.0.0.1", 3001)
    book.add_address(good, src)
    book.add_address(bad, src)
    kb = book._addrs[str(bad)]
    kb.attempts = ab.MAX_FAILURES
    kb.last_attempt = 1.0  # long ago, never succeeded -> bad
    for _ in range(50):
        picked = book.pick_address(new_bias_pct=100)
        assert str(picked) == str(good)


def test_addrbook_need_more_addrs():
    from tendermint_tpu.p2p import addrbook as ab

    book = AddrBook("", routability_strict=False)
    assert book.need_more_addrs()
    assert ab.NEED_ADDRESS_THRESHOLD == 1000


def test_addrbook_pick_recovers_when_all_bad():
    """After an outage burns attempts on every address, pick_address must
    fall back to retrying them, never strand the node (code-review r3)."""
    from tendermint_tpu.p2p import addrbook as ab

    book = AddrBook("", routability_strict=False)
    src = NetAddress("127.0.0.1", 1)
    for port in (4000, 4001):
        a = NetAddress("127.0.0.1", port)
        book.add_address(a, src)
        ka = book._addrs[str(a)]
        ka.attempts = ab.MAX_FAILURES
        ka.last_attempt = 1.0  # never succeeded, long ago -> is_bad
    assert book.pick_address() is not None


def test_pex_flood_eviction_requires_ip_match():
    """A flooder claiming a victim's listen_addr must not evict it from
    the book; only an address matching the socket IP is marked bad."""
    from tendermint_tpu.p2p.pex import PEXReactor

    book = AddrBook("", routability_strict=False)
    victim = NetAddress("127.0.0.1", 5555)
    book.add_address(victim, victim)

    class FakeStream:
        @staticmethod
        def remote_addr():
            return "10.9.9.9:1234"  # attacker's real socket IP

    class FakePeer:
        node_info = type(
            "NI", (), {"listen_addr": "127.0.0.1:5555"}
        )()  # claims the victim's address
        stream = FakeStream()

        @staticmethod
        def id():
            return "attacker"

    class FakeSwitch:
        stopped = []

        def stop_peer_for_error(self, peer, reason):
            self.stopped.append((peer, reason))

    pex = PEXReactor(book, ensure_peers_period=3600)
    pex.switch = FakeSwitch()
    pex._msg_counts["attacker"] = [time.monotonic()] * 1001  # over limit
    pex.receive(0x00, FakePeer(), b"{}")
    assert str(victim) in book._addrs  # victim survives
    assert pex.switch.stopped  # flooder still disconnected


def test_recv_routine_never_inherits_the_admission_timeout():
    """Round-17 regression for the full-suite fast-sync flake ("stream
    closed" on both sides, B stuck at 0): Switch.add_peer_from_stream
    arms a handshake timeout on the RAW socket and only restores
    blocking mode AFTER add_peer returns — but peer.start() (inside
    add_peer) launches the mconn recv routine first, and CPython fixes
    a recv's deadline at call entry, so the first blocking read
    inherited the armed timeout. A link quiet past that budget (mconn
    pings only every 40 s; under full-suite load the remote's first
    sends can be arbitrarily late) then tripped the timeout, which
    SocketStream.read reports as EOF — the connection died as
    ConnectionError("stream closed") with nothing wrong on the wire.

    The deterministic interleaving: arm a short admission timeout, let
    the peer start (recv enters with it armed), restore blocking mode a
    beat later exactly as the switch does, stay SILENT past the armed
    budget, then speak. Pre-fix the message is lost and on_error fires
    "stream closed"; post-fix (Peer.on_start clears the raw socket's
    timeout before the recv routine launches) the peer survives."""
    import socket as _socket
    import struct as _struct

    from tendermint_tpu.p2p.peer import Peer, PeerConfig
    from tendermint_tpu.p2p.stream import SocketStream

    a, b = _socket.socketpair()
    a.settimeout(0.4)  # the switch's admission arming
    got, errs = [], []
    peer = Peer(
        SocketStream(a),
        outbound=False,
        channel_descs=[ChannelDescriptor(id=0x20)],
        on_receive=lambda p, ch, msg: got.append((ch, msg)),
        on_error=lambda p, exc: errs.append(exc),
        config=PeerConfig(auth_enc=False),
        node_priv_key=gen_priv_key_ed25519(),
    )
    peer.start()           # recv routine enters its first blocking read
    time.sleep(0.05)
    a.settimeout(None)     # the finally in add_peer_from_stream — which
    # pre-fix was too late for the already-parked recv call
    try:
        time.sleep(1.0)    # silent link, well past the armed 0.4 s
        payload = b"hello-after-quiet"
        b.sendall(
            _struct.pack(">BBBH", 0x02, 0x20, 1, len(payload)) + payload
        )
        assert wait_until(lambda: got, timeout=5), (
            f"message lost; connection errors: {errs}"
        )
        assert got[0] == (0x20, payload)
        assert not errs, f"connection fataled on a healthy quiet link: {errs}"
    finally:
        peer.stop()
        b.close()


# -- round-18 adversarial-tier hardening regressions --------------------------
#
# Each hole below was exposed by the hostile-peer family in
# tests/netchaos_common.py (slow-loris, oversized-frame, eclipse); per
# the issue discipline every fix gets a deterministic UNIT regression
# here, not just a scenario.


def test_node_info_dribble_hits_absolute_deadline():
    """Slow-loris against the NodeInfo phase: the admission timeout used
    to bound each socket READ, so a peer feeding one byte per
    just-under-the-budget interval could hold the admission thread for
    MAX_NODE_INFO_SIZE reads. exchange_node_info's deadline is now
    ABSOLUTE — a dribbler whose every byte lands comfortably within the
    per-read budget still trips it at the total budget."""
    import socket as _socket
    import struct as _struct

    from tendermint_tpu.p2p.peer import exchange_node_info
    from tendermint_tpu.p2p.stream import SocketStream

    a, b = _socket.socketpair()
    info = NodeInfo(
        pub_key=gen_priv_key_ed25519().pub_key(),
        moniker="m", network="n", version=default_version("t"),
    )
    stop = threading.Event()

    def dribble():
        try:
            b.recv(65536)  # drain the honest side's own info
            b.sendall(_struct.pack(">I", 512))  # plausible length claim
            while not stop.is_set():
                b.sendall(b"x")  # one byte per beat: every READ succeeds
                stop.wait(0.15)
        except OSError:
            pass

    t = threading.Thread(target=dribble, daemon=True)
    t.start()
    t0 = time.monotonic()
    try:
        with pytest.raises(ConnectionError, match="timed out"):
            exchange_node_info(SocketStream(a), info, timeout=0.8)
        took = time.monotonic() - t0
        # absolute, not per-read: the per-read budget alone would NEVER
        # fire here (each byte arrives within 0.15 s)
        assert took < 5.0, f"deadline not absolute: took {took:.1f}s"
    finally:
        stop.set()
        for s in (a, b):
            try:
                s.close()
            except OSError:
                pass


def test_secretconn_oversized_frame_claim_refused_before_buffering():
    """Oversized-frame adversary: a frame length claim beyond the legal
    maximum (DATA_MAX_SIZE + 16-byte tag) is refused the moment the
    claim is read. The old path tried to BUFFER the claimed payload
    first — an attacker claiming 64 KiB and sending nothing parked the
    reader forever, and one sending junk cost a 64 KiB buffer per frame
    just to fail the AEAD tag."""
    import struct as _struct

    from tendermint_tpu.libs import telemetry
    from tendermint_tpu.p2p.secret_connection import (
        DATA_MAX_SIZE,
        SecretConnectionError,
    )

    a, b = pipe_pair()
    ka, kb = gen_priv_key_ed25519(), gen_priv_key_ed25519()
    out = {}
    t = threading.Thread(
        target=lambda: out.update(conn=SecretConnection(b, kb)), daemon=True
    )
    t.start()
    ca = SecretConnection(a, ka)
    t.join(5)
    reg = telemetry.default_registry()
    over0 = reg.counter("p2p_secretconn_oversized_frames_total").value

    # an illegal claim with NO payload behind it: pre-fix this blocked
    # the reader; post-fix it raises immediately
    ca.stream.write(_struct.pack(">H", DATA_MAX_SIZE + 17))
    with pytest.raises(SecretConnectionError, match="oversized"):
        out["conn"].read(10)
    # poisoned forever, and counted
    with pytest.raises(SecretConnectionError):
        out["conn"].read(1)
    assert reg.counter(
        "p2p_secretconn_oversized_frames_total"
    ).value == over0 + 1
    ca.close()


def test_reactor_recv_ceilings_right_sized():
    """The per-channel reassembly ceilings are right-sized to each
    channel's largest LEGAL message (round 18): before, every channel
    inherited the 21 MiB block ceiling, so an oversized-frame peer
    could park ~147 MiB of never-delivered reassembly bytes across one
    connection's channels."""
    from tendermint_tpu.codec import jsonval as jv
    from tendermint_tpu.consensus.reactor import (
        ConsensusReactor,
        DATA_CHANNEL,
        STATE_CHANNEL,
        VOTE_CHANNEL,
        VOTE_SET_BITS_CHANNEL,
    )
    from tendermint_tpu.mempool.reactor import MempoolReactor
    from tendermint_tpu.p2p.pex import PEXReactor

    from tendermint_tpu.types.params import MAX_BLOCK_PART_SIZE_BYTES

    caps = {
        d.id: d.recv_message_capacity
        for d in ConsensusReactor.get_channels(None)
    }
    assert caps[VOTE_CHANNEL] == 1 << 16  # a vote is ~700 B
    assert caps[STATE_CHANNEL] == 1 << 16
    assert caps[VOTE_SET_BITS_CHANNEL] == 1 << 16
    # the DATA cap DERIVES from the params-validated part-size bound
    # (hex-doubled + envelope headroom) so a legal genesis can never
    # configure a part the channel refuses
    assert caps[DATA_CHANNEL] == 2 * MAX_BLOCK_PART_SIZE_BYTES + (1 << 16)
    assert caps[DATA_CHANNEL] < 1 << 20
    [mp] = MempoolReactor.get_channels(None)
    # ... but a MAX_TX_BYTES tx must still FIT (hex-doubled + envelope)
    assert mp.recv_message_capacity >= 2 * jv.MAX_TX_BYTES
    assert mp.recv_message_capacity < 10 * (1 << 20)
    [px] = PEXReactor.get_channels(None)
    assert px.recv_message_capacity == 1 << 16
    # ... and genesis validation refuses a part size the channel could
    # not carry (the binding that keeps cap and params consistent)
    from tendermint_tpu.types.params import ConsensusParams

    cp = ConsensusParams()
    cp.block_gossip.block_part_size_bytes = MAX_BLOCK_PART_SIZE_BYTES + 1
    err = cp.validate()
    assert err is not None and "recv ceiling" in err
    cp.block_gossip.block_part_size_bytes = MAX_BLOCK_PART_SIZE_BYTES
    assert cp.validate() is None


def test_vote_channel_reassembly_past_ceiling_drops_peer():
    """Behavioral half of the ceiling regression: streaming a message
    past the vote channel's 64 KiB bound errors the connection (the
    switch then drops the peer for cause) instead of buffering toward
    the old 21 MiB."""
    from tendermint_tpu.consensus.reactor import ConsensusReactor, VOTE_CHANNEL

    descs = ConsensusReactor.get_channels(None)
    ma, mb, recv_a, recv_b, err = _mconn_pair(descs=descs)
    try:
        assert ma.send(VOTE_CHANNEL, b"\x00" * (1 << 17))  # 128 KiB
        assert wait_until(lambda: err, timeout=5), "oversize never errored"
        assert any("exceeds" in str(e) for e in err), err
        assert not recv_b, "oversized message must never be delivered"
    finally:
        ma.stop()
        mb.stop()


def test_fuzzed_stream_corrupts_deterministically():
    """The frame-corruption wrapper (p2p/fuzz.py, round-18 audit): the
    broken-against-SecretConnection silent write-DROP mode is gone;
    prob_corrupt XORs one byte per write, seeded-deterministic."""
    from tendermint_tpu.p2p.fuzz import FuzzedStream

    outs = []
    for _ in range(2):
        a, b = pipe_pair()
        fa = FuzzedStream(a, prob_corrupt=1.0, seed=3)
        fa.write(b"AAAABBBB")
        got = b.read(100)
        outs.append(got)
        assert got != b"AAAABBBB" and len(got) == 8
        assert sum(x != y for x, y in zip(got, b"AAAABBBB")) == 1
        assert fa.corrupted_writes == 1
        fa.close()
        b.close()
    assert outs[0] == outs[1], "same seed must corrupt identically"
    # and the drop mode is really gone — the constructor refuses it
    a, b = pipe_pair()
    with pytest.raises(TypeError):
        FuzzedStream(a, prob_drop_rw=0.5)
    a.close()
    b.close()


def test_fuzz_corruption_is_loud_tamper_under_secretconn():
    """The frame-corruption peer end to end: a FuzzedStream UNDER the
    SecretConnection makes a corrupted write ciphertext tamper on the
    wire — the receiving AEAD must raise (never EOF) and count it."""
    from tendermint_tpu.libs import telemetry
    from tendermint_tpu.p2p.fuzz import FuzzedStream
    from tendermint_tpu.p2p.secret_connection import SecretConnectionError

    a, b = pipe_pair()
    fa = FuzzedStream(a, prob_corrupt=0.0, seed=5)  # clean handshake
    out = {}
    t = threading.Thread(
        target=lambda: out.update(
            conn=SecretConnection(b, gen_priv_key_ed25519())
        ),
        daemon=True,
    )
    t.start()
    ca = SecretConnection(fa, gen_priv_key_ed25519())
    t.join(5)
    reg = telemetry.default_registry()
    af0 = reg.counter("p2p_secretconn_auth_failures_total").value
    fa.prob_corrupt = 1.0  # every frame from now on arrives tampered
    ca.write(b"this frame will not verify")
    with pytest.raises(SecretConnectionError):
        out["conn"].read(10)
    assert fa.corrupted_writes >= 1
    assert reg.counter("p2p_secretconn_auth_failures_total").value > af0
    ca.close()


def test_ip_range_counter_boundary_and_churn_races():
    """Eclipse backing, unit level: the range counter at the limit
    boundary under add/remove churn — a slot freed by a leaving peer is
    immediately claimable, concurrent add/remove pairs never leak or
    steal counts, and the counter lands exactly at zero."""
    from tendermint_tpu.p2p.ip_range_counter import IPRangeCounter

    # boundary: at the limit, refuse; free one slot, admit exactly one
    c = IPRangeCounter(limits=(2, 2, 2))
    assert c.try_add("9.9.9.1")
    assert c.try_add("9.9.9.2")
    assert not c.try_add("9.9.9.3")  # /24 full
    c.remove("9.9.9.1")
    assert c.try_add("9.9.9.3")      # freed slot claimable
    assert not c.try_add("9.9.9.4")  # and only that one
    # a refused add must not have half-counted any depth
    assert c.count("9") == 2 and c.count("9.9") == 2 and c.count("9.9.9") == 2

    # churn: racing add/remove pairs across threads; paired ops must
    # cancel exactly (no leaked counts to starve later honest peers —
    # the round-12 leak's failure shape — and no negative underflow)
    c2 = IPRangeCounter(limits=(64, 32, 16))
    errs = []

    def churn(tid):
        try:
            for i in range(300):
                ip = f"10.0.{tid % 3}.{i % 7}"
                if c2.try_add(ip):
                    c2.remove(ip)
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    threads = [
        threading.Thread(target=churn, args=(t,), daemon=True)
        for t in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs
    for p in ("10", "10.0", "10.0.0", "10.0.1", "10.0.2"):
        assert c2.count(p) == 0, (p, c2.count(p))


def test_uncount_stream_releases_exactly_once_across_wrapper_chain():
    """The round-12 wrapper-chain uncount under churn: the count marker
    lives on the RAW stream under fuzz/secret wrappers; releasing twice
    (error path + removal path racing) must not steal a still-live
    peer's count from the same range."""
    import socket as _socket

    from tendermint_tpu.p2p.fuzz import FuzzedStream
    from tendermint_tpu.p2p.stream import SocketStream

    sw = Switch()
    assert sw.ip_ranges.try_add("10.1.2.3")  # peer A
    assert sw.ip_ranges.try_add("10.1.2.4")  # peer B, same /24

    s1, s2 = _socket.socketpair()
    raw = SocketStream(s1)
    raw.counted_ip = "10.1.2.3"

    class _Outer:  # a secret-connection-shaped wrapper
        def __init__(self, stream):
            self.stream = stream

    chain = _Outer(FuzzedStream(raw))
    sw._uncount_stream(chain)
    assert sw.ip_ranges.count("10.1.2") == 1  # A released
    # the double-release race: a second uncount finds the marker cleared
    sw._uncount_stream(chain)
    assert sw.ip_ranges.count("10.1.2") == 1, "double uncount stole B's count"
    for s in (s1, s2):
        s.close()


def test_addrbook_one_slash24_cannot_dominate_the_book():
    """Eclipse backing, addr-book level: hundreds of addresses from one
    /24 (one attacker subnet, one source) collapse into the few buckets
    their (group, source-group) hash allows, so they evict EACH OTHER —
    while a handful of diverse addresses stay present and pickable."""
    import random as _random

    book = AddrBook()
    book._rng = _random.Random(7)
    src = NetAddress("9.9.9.1", 26656)
    for i in range(500):
        book.add_address(NetAddress(f"9.9.9.{i % 250}", 10000 + i), src)
    diverse = []
    for i in range(20):
        a = NetAddress(f"{20 + i}.{i + 1}.0.1", 26656)
        diverse.append(a)
        book.add_address(a, a)

    doms = [k for k in book._addrs if k.startswith("9.9.9.")]
    # one (group, src-group) pair hashes to at most NEW_BUCKETS_PER_ADDRESS
    # buckets of BUCKET_SIZE — the 500 dials cannot occupy more
    from tendermint_tpu.p2p.addrbook import (
        BUCKET_SIZE,
        NEW_BUCKETS_PER_ADDRESS,
    )

    assert len(doms) <= NEW_BUCKETS_PER_ADDRESS * BUCKET_SIZE, len(doms)
    # every diverse address survived the flood
    for a in diverse:
        assert str(a) in book._addrs
    # and the picker still reaches them (seeded: deterministic)
    picked_diverse = sum(
        1 for _ in range(300)
        if not str(book.pick_address()).startswith("9.9.9.")
    )
    assert picked_diverse >= 10, picked_diverse
