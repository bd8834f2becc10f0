"""The delay line alone (p2p/delay_line.py): a frame written at t leaves
at t + d and never before, FIFO on a link, the writer returns at once,
one timer thread over all of a node's links, under a SecretConnection the
AEAD counters stay in step, a link closed with chunks queued ends
cleanly, no library or no socket = refused, not another timer; and its
way in: the `[p2p]` fields through the TOML round trip and the CLI, the
region in NodeInfo, a peer with an unknown or missing region refused, no
configuration = no wrapper and no thread. Every link here is one end of
a socket pair, as every link of a node is a socket.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from tendermint_tpu.config import load_config
from tendermint_tpu.config.config import default_config
from tendermint_tpu.config.toml import config_to_toml, ensure_root
from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
from tendermint_tpu.p2p.delay_line import (
    DelayedStream,
    DelayLine,
    LinkDelays,
    parse_rtt_table,
    region_of,
)
from tendermint_tpu.p2p.node_info import NodeInfo, default_version
from tendermint_tpu.p2p.peer import PeerConfig
from tendermint_tpu.p2p.stream import pipe_pair
from tendermint_tpu.p2p.switch import Switch

TABLE = "a:a=1,a:b=40,b:b=1,a:c=90,b:c=60,c:c=2"


def _line_threads() -> list:
    """Every timer thread of this process, by the name it gives itself
    (/proc: it is no Python thread)."""
    found = []
    for task in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{task}/comm") as f:
                if f.read().strip() == "p2p.delayLine":
                    found.append(task)
        except OSError:
            pass                      # the thread ended meanwhile
    return found


@pytest.fixture
def line():
    ln = DelayLine()
    yield ln
    ln.stop()


def _wait_for(cond, timeout=3.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.002)
    return cond()


class _Far:
    """The far end of a socket pair: a thread that notes when each frame
    of `size` bytes was complete."""

    def __init__(self, size: int):
        self.near, self._far = pipe_pair()
        self.size = size
        self.frames: list[tuple[float, bytes]] = []
        self.eof = threading.Event()
        self._t = threading.Thread(target=self._read, daemon=True)
        self._t.start()

    def _read(self):
        buf = b""
        while True:
            chunk = self._far.read(65536)
            if not chunk:
                self.eof.set()
                return
            buf += chunk
            while len(buf) >= self.size:
                self.frames.append((time.monotonic(), buf[:self.size]))
                buf = buf[self.size:]

    def close(self):
        self._far.close()
        self._t.join(2.0)


# -- the line -------------------------------------------------------------------


def test_a_frame_is_never_early_in_order_and_the_writer_does_not_wait(line):
    far = _Far(8)
    s = DelayedStream(far.near, line)
    s.write(b"shake-00")                       # before the delay: straight through
    assert _wait_for(lambda: len(far.frames) == 1)
    s.set_delay(0.25, "b")
    stamps = []
    t0 = time.monotonic()
    for i in range(200):
        stamps.append(time.monotonic())
        s.write(b"frame%03d" % i)
    assert time.monotonic() - t0 < 0.2, "the writer waited"
    assert len(far.frames) == 1                # nothing has left yet
    assert _wait_for(lambda: len(far.frames) == 201)
    assert [d for _t, d in far.frames] == [b"shake-00"] + [b"frame%03d" % i for i in range(200)]
    for written_at, (arrived_at, _d) in zip(stamps, far.frames[1:]):
        assert arrived_at - written_at >= 0.25
    st = s.stats()
    assert st["frames"] == 200 and st["bytes"] == 1600 and 1 <= st["queue_max"] <= 200
    assert st["region"] == "b" and st["delay_s"] == 0.25
    assert 0.0 < st["late_max_s"] <= st["late_sum_s"]
    # the node says what its histogram's buckets are: a reader needs no
    # constant of the program
    edges = st["late_edges_s"]
    assert edges == sorted(edges) and edges[0] > 0 and len(edges) >= 10
    assert sum(st["late_hist"]) == 200 and len(st["late_hist"]) == len(edges) + 1
    assert "p2p.delayLine" not in [t.name for t in threading.enumerate()]
    assert len(_line_threads()) == 1           # the native one; no Python timer
    s.close()
    far.close()


def test_order_holds_across_a_change_of_delay(line):
    far = _Far(3)
    s = DelayedStream(far.near, line)
    s.write(b"hsk")                            # no delay yet: straight through
    s.set_delay(0.05)
    for i in range(100):
        s.write(b"%03d" % i)
    s.set_delay(0.02)                          # the same link, the same timer
    for i in range(100, 200):
        s.write(b"%03d" % i)
    assert _wait_for(lambda: len(far.frames) == 201)
    assert [d for _t, d in far.frames] == [b"hsk"] + [b"%03d" % i for i in range(200)]
    assert s.stats()["delay_s"] == 0.02
    s.close()
    far.close()


def test_two_writers_of_one_link_keep_the_order_they_were_stamped_in(line):
    far = _Far(4)
    s = DelayedStream(far.near, line)
    s.set_delay(0.01)
    order, mtx = [], threading.Lock()

    def writer(tag):
        for i in range(100):
            data = b"%s%03d" % (tag, i)
            with mtx:                 # what MConnection's write lock does
                order.append(data)
                s.write(data)

    ts = [threading.Thread(target=writer, args=(t,)) for t in (b"x", b"y", b"z")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5.0)
        assert not t.is_alive()
    assert _wait_for(lambda: len(far.frames) == 300)
    assert [d for _t, d in far.frames] == order
    s.close()
    far.close()


def test_one_thread_serves_every_link_and_a_closed_link_ends_cleanly(line):
    before = len(_line_threads())
    fars = [_Far(1) for _ in range(6)]
    links = [DelayedStream(f.near, line) for f in fars]
    assert len(_line_threads()) == before      # none until a link has a delay
    t0 = time.monotonic()
    for i, s in enumerate(links):
        s.set_delay(0.03 * (6 - i))            # the first link is the slowest
        s.write(b"x")
    assert _wait_for(lambda: len(_line_threads()) == before + 1)
    assert _wait_for(lambda: all(len(f.frames) == 1 for f in fars))
    arrived = [f.frames[0][0] - t0 for f in fars]
    for i, at in enumerate(arrived):
        assert at >= 0.03 * (6 - i)
    assert arrived == sorted(arrived, reverse=True)   # each link its own delay
    # close the slowest link with chunks queued: they never arrive, the far
    # end sees the end of the stream, the other links go on
    for _ in range(10):
        links[0].write(b"n")
    links[0].close()
    with pytest.raises(ConnectionError):
        links[0].write(b"after the close")
    assert fars[0].eof.wait(2.0) and len(fars[0].frames) == 1
    links[5].write(b"y")
    assert _wait_for(lambda: len(fars[5].frames) == 2)
    assert links[0].stats()["frames"] == 1
    assert len(_line_threads()) == before + 1
    for s, f in zip(links[1:], fars[1:]):
        s.close()
        f.close()


def test_a_write_that_fails_reaches_the_writer_and_the_reader(line):
    near, far = pipe_pair()
    s = DelayedStream(near, line)
    s.set_delay(0.01)
    far.close()                                # the peer is gone
    s.write(b"x" * 4096)                       # queued: nobody knows yet

    def broken():
        try:
            s.write(b"y")
        except ConnectionError:
            return True
        return False
    assert _wait_for(broken)                   # the writer at a later write
    assert near.read(16) == b""                # the reader by the end of file
    s.close()


def test_stopping_the_line_ends_the_thread_and_refuses_writes():
    ln = DelayLine()
    far = _Far(4)
    s = DelayedStream(far.near, ln)
    before = len(_line_threads())
    s.set_delay(0.2)
    s.write(b"late")
    assert _wait_for(lambda: len(_line_threads()) == before + 1)
    ln.stop()
    assert _wait_for(lambda: len(_line_threads()) == before)
    with pytest.raises(ConnectionError):
        s.write(b"more")
    assert s.stats()["frames"] == 0 and far.frames == []
    other = DelayedStream(_Far(1).near, ln)
    with pytest.raises(ConnectionError):
        other.set_delay(0.1)                   # no new link on a stopped line
    s.close()
    far.close()


def test_under_a_secret_connection_the_frames_arrive_whole_and_in_order(line):
    from tendermint_tpu.p2p.secret_connection import SecretConnection

    a, b = pipe_pair()
    delayed = DelayedStream(a, line)
    ends = {}

    def shake(name, stream):
        ends[name] = SecretConnection(stream, gen_priv_key_ed25519())

    ts = [threading.Thread(target=shake, args=("a", delayed)),
          threading.Thread(target=shake, args=("b", b))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10.0)
        assert not t.is_alive()
    delayed.set_delay(0.3)
    t0 = time.monotonic()
    sent = [bytes([i]) * (1 + 37 * i) for i in range(40)]   # frames of every size
    for m in sent:
        ends["a"].write(m)
    assert time.monotonic() - t0 < 0.25, "the writer waited"
    want = b"".join(sent)
    got = b""
    while len(got) < len(want):
        chunk = ends["b"].read(4096)
        assert chunk, "the AEAD lost step"
        got += chunk
    assert got == want and time.monotonic() - t0 >= 0.3
    # the other direction is this end's alone to delay: undelayed here
    ends["b"].write(b"back")
    assert ends["a"].read(16) == b"back"
    ends["a"].close()


# -- one timer, and no other -------------------------------------------------------


class _NoSocket:
    """A stream that is no socket (an in-process fabric)."""

    def __init__(self):
        self.wrote = []

    def write(self, data):
        self.wrote.append(bytes(data))

    def read(self, n):
        return b""

    def close(self):
        pass


def test_a_stream_without_a_socket_is_refused_a_delay(line):
    inner = _NoSocket()
    s = DelayedStream(inner, line)
    s.write(b"handshake")                      # undelayed, any stream will do
    assert inner.wrote == [b"handshake"]
    s.set_delay(0.0, "a")                      # no delay asked for: still fine
    with pytest.raises(ConnectionError, match="socket"):
        s.set_delay(0.01, "b")
    assert not _line_threads()


@pytest.mark.parametrize("lacks", ["the library", "the entry points"])
def test_without_the_library_delays_are_a_configuration_error(monkeypatch, lacks):
    """A checkout whose `make -C native` fails, or whose library dates
    from before the delay line, must not run the net on another timer:
    the node refuses the configuration; one without delays starts."""
    from tendermint_tpu import native

    if lacks == "the library":
        monkeypatch.setattr(native, "get_lib", lambda: None)
    else:
        monkeypatch.setattr(native, "get_lib", lambda: object())
    monkeypatch.setattr(native, "_delay_line", None)
    cfg = default_config()
    assert LinkDelays.from_config(cfg.p2p) is None
    cfg.p2p.test_link_region, cfg.p2p.test_link_rtt_ms = "a", TABLE
    with pytest.raises(ValueError, match="native delay line"):
        LinkDelays.from_config(cfg.p2p)
    with pytest.raises(RuntimeError):
        DelayLine()
    assert not _line_threads()


def test_a_node_with_delays_configured_refuses_to_start_without_the_library(
        tmp_path, monkeypatch):
    from tendermint_tpu import native
    from tendermint_tpu.config.toml import reset_test_root
    from tendermint_tpu.node.node import default_new_node

    cfg = reset_test_root(str(tmp_path / "home"))
    cfg.rpc.laddr = cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.test_link_region, cfg.p2p.test_link_rtt_ms = "a", TABLE
    monkeypatch.setattr(native, "get_lib", lambda: None)
    monkeypatch.setattr(native, "_delay_line", None)
    with pytest.raises(ValueError, match="p2p.test_link_.*native delay line"):
        default_new_node(cfg)
    assert not _line_threads()
    # with the library the same home gives a node
    monkeypatch.undo()
    node = default_new_node(cfg)
    assert node.sw.peer_config.link_delays.region == "a"
    node.sw.peer_config.link_delays.line.stop()


# -- the table ------------------------------------------------------------------


def test_the_table_is_symmetric_complete_and_round_trips():
    t = parse_rtt_table(TABLE, must_hold="b")
    assert t[("a", "b")] == t[("b", "a")] == 40.0 and t[("c", "c")] == 2.0
    assert len(t) == 9
    assert parse_rtt_table(" a:b = 40 ,b:b=1, a:a=1,c:c=2,c:a=90,c:b=60,") == t
    for bad in ("a:a=1,a:b=40",                 # b:b missing
                "a:a=1,a:b=40,b:b=1,b:a=41",    # two values for one pair
                "a:a=1,a-b=40", "a:a=x", "a:a=-1"):
        with pytest.raises(ValueError):
            parse_rtt_table(bad)
    with pytest.raises(ValueError):
        parse_rtt_table(TABLE, must_hold="d")


def test_link_delays_from_config_and_the_one_way_delay():
    cfg = default_config()
    assert LinkDelays.from_config(cfg.p2p) is None
    cfg.p2p.test_link_region = "a"
    with pytest.raises(ValueError):
        LinkDelays.from_config(cfg.p2p)         # a region and no table
    cfg.p2p.test_link_rtt_ms = TABLE
    d = LinkDelays.from_config(cfg.p2p)
    assert d.region == "a"
    assert d.one_way_s("b") == pytest.approx(0.020)
    assert d.one_way_s("a") == pytest.approx(0.0005)
    for region in (None, "", "mars"):
        with pytest.raises(ConnectionError):
            d.one_way_s(region)


def test_the_fields_through_the_toml_round_trip(tmp_path):
    cfg = ensure_root(str(tmp_path))
    assert cfg.p2p.test_link_region == "" and cfg.p2p.test_link_rtt_ms == ""
    assert 'test_link_region = ""' in config_to_toml(cfg)
    cfg.p2p.test_link_region = "ap-southeast-2"
    cfg.p2p.test_link_rtt_ms = TABLE
    with open(tmp_path / "config.toml", "w") as f:
        f.write(config_to_toml(cfg))
    back = load_config(str(tmp_path))
    assert back.p2p.test_link_region == "ap-southeast-2"
    assert back.p2p.test_link_rtt_ms == TABLE


def test_the_cli_takes_the_fields():
    from tendermint_tpu import cli

    args = cli.build_parser().parse_args(
        ["--home", "/tmp/x", "node", "--p2p.test_link_region", "b",
         "--p2p.test_link_rtt_ms", TABLE])
    assert (args.test_link_region, args.test_link_rtt_ms) == ("b", TABLE)
    args = cli.build_parser().parse_args(["--home", "/tmp/x", "node"])
    assert args.test_link_region is None and args.test_link_rtt_ms is None


# -- two switches ---------------------------------------------------------------


def _switch(region: str | None, table: str = TABLE, announce: str | None = "same"):
    cfg = default_config()
    if region is not None:
        cfg.p2p.test_link_region, cfg.p2p.test_link_rtt_ms = region, table
    delays = LinkDelays.from_config(cfg.p2p)
    sw = Switch(cfg.p2p, PeerConfig(link_delays=delays))
    info = NodeInfo(pub_key=sw.node_priv_key.pub_key(), moniker="t",
                    network="delay-test", version=default_version("0.0.0"))
    said = region if announce == "same" else announce
    if said is not None:
        info.other.append(f"region={said}")
    sw.set_node_info(info)
    return sw


def _connect(sw_a: Switch, sw_b: Switch):
    """Both ends of one pipe through add_peer_from_stream; returns
    {name: peer or the exception that refused it}."""
    a, b = pipe_pair()
    out = {}

    def admit(name, sw, stream, outbound):
        try:
            out[name] = sw.add_peer_from_stream(stream, outbound=outbound)
        except Exception as exc:  # noqa: BLE001 — what the test looks at
            out[name] = exc
            stream.close()

    ts = [threading.Thread(target=admit, args=("a", sw_a, a, True)),
          threading.Thread(target=admit, args=("b", sw_b, b, False))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(15.0)
        assert not t.is_alive()
    return out


def test_each_end_sets_its_side_of_the_link_and_the_ping_sees_both():
    sw_a, sw_b = _switch("a"), _switch("c")
    sw_a.start()
    sw_b.start()
    try:
        peers = _connect(sw_a, sw_b)
        pa, pb = peers["a"], peers["b"]
        assert region_of(pa.node_info) == "c" and region_of(pb.node_info) == "a"
        assert pa.link.delay_s == pb.link.delay_s == pytest.approx(0.045)
        assert pa.link.region == "c" and pb.link.region == "a"
        # a link pings as it starts: both ends hold a sample within a
        # round trip or two, and none is under the configured 90 ms
        assert _wait_for(lambda: pa.rtt_s() is not None and pb.rtt_s() is not None)
        for p in (pa, pb):
            rec = p.status()["rtt"]
            assert rec["count"] == 1 and rec["min_s"] >= 0.090
            assert rec["last_s"] == rec["min_s"] == rec["smoothed_s"]
            assert p.status()["link"]["frames"] >= 1
        # one timer thread a node, whatever its links
        assert len(_line_threads()) == 2
    finally:
        sw_a.stop()
        sw_b.stop()
    assert _wait_for(lambda: not _line_threads())


def test_an_end_that_delays_too_little_is_seen_by_both_ends_pings():
    """The control of the cell's `links_with_rtt_under_configured`, on two
    real switches: b's table says 2 ms where the net's says 90, so its
    line lets its side of the link out 44 ms early. Neither end's ping
    can be told otherwise: out in 45 and back in 1, or the reverse."""
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import wan_judge
    from reference import wan_ref

    sw_a = _switch("a")
    sw_b = _switch("c", table=TABLE.replace("a:c=90", "a:c=2"))
    sw_a.start()
    sw_b.start()
    try:
        peers = _connect(sw_a, sw_b)
        pa, pb = peers["a"], peers["b"]
        assert pa.link.delay_s == pytest.approx(0.045)
        assert pb.link.delay_s == pytest.approx(0.001)
        assert _wait_for(lambda: pa.rtt_s() is not None and pb.rtt_s() is not None)
        for p in (pa, pb):
            assert 0.046 <= p.status()["rtt"]["min_s"] < 0.090
        net = wan_ref.WanNet(["a", "c"], {"a:a": 1, "c:c": 2, "a:c": 90}, 2)
        links = {(0, 1): {"rtt": pa.status()["rtt"], "link": pa.status()["link"]},
                 (1, 0): {"rtt": pb.status()["rtt"], "link": pb.status()["link"]}}
        _records, missing, under = wan_judge.link_records(net, links)
        assert missing == [] and under == [(0, 1), (1, 0)]
        # and by the round trips alone, were b to report the delay it should have
        links[(1, 0)]["link"]["delay_s"] = 0.045
        assert wan_judge.link_records(net, links)[2] == [(0, 1), (1, 0)]
    finally:
        sw_a.stop()
        sw_b.stop()
    assert _wait_for(lambda: not _line_threads())


@pytest.mark.parametrize("announce", [None, "mars"])
def test_a_peer_with_a_missing_or_unknown_region_is_refused(announce):
    sw_a, sw_b = _switch("a"), _switch("b", announce=announce)
    sw_a.start()
    sw_b.start()
    try:
        peers = _connect(sw_a, sw_b)
        assert isinstance(peers["a"], ConnectionError)
        assert "region" in str(peers["a"])
        assert sw_a.peers.size() == 0
    finally:
        sw_a.stop()
        sw_b.stop()
    assert _wait_for(lambda: not _line_threads())


def test_with_nothing_configured_the_chain_holds_no_delay_line_and_no_thread():
    assert _wait_for(lambda: not _line_threads())   # an earlier test's, ending
    sw_a, sw_b = _switch(None), _switch(None)
    assert sw_a.peer_config.link_delays is None
    sw_a.start()
    sw_b.start()
    try:
        peers = _connect(sw_a, sw_b)
        for p in peers.values():
            assert p.link is None and "link" not in p.status()
            obj, kinds = p.stream, []
            while obj is not None:
                kinds.append(type(obj).__name__)
                obj = getattr(obj, "stream", None)
            assert kinds == ["SecretConnection", "SocketStream"]
            assert region_of(p.node_info) is None
        # the first ping still leaves at once, on loopback as anywhere
        assert _wait_for(lambda: all(p.rtt_s() is not None for p in peers.values()))
        assert not _line_threads()
    finally:
        sw_a.stop()
        sw_b.stop()
