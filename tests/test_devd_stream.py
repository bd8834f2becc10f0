"""Streamed devd transport tests (tendermint_tpu/devd.py verify_stream):
verdict parity against the single-shot op and the CPU reference, protocol
edges (empty batch, 1 item, chunk-width remainders, malformed mid-stream
frames), pipelining (the daemon accepts chunk N+1 while chunk N is in the
kernel — proven by the in-flight high-water counter), and client
reconnect across a daemon restart.

Parity runs against a real CPU-kernel daemon subprocess (the same IPC
bytes a TPU daemon serves); behavioral tests ride the sim-device daemon
(TENDERMINT_DEVD_SIM_RATE — no jax, instant startup, deterministic
device time).
"""

from __future__ import annotations

import os
import socket
import struct
import subprocess
import sys
import time

import pytest

from tendermint_tpu import devd
from tendermint_tpu.crypto import ed25519 as ed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(sock: str, extra_env: dict) -> subprocess.Popen:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "TENDERMINT_DEVD_SOCK": sock,
        "TENDERMINT_DEVD_ACCEPT_CPU": "1",
        "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
        **extra_env,
    }
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.devd"],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
    )


def _wait_held(client, proc, deadline_s: float) -> None:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if proc.poll() is not None:
            err = proc.stderr.read() if proc.stderr else b""
            pytest.fail(f"daemon died: {err[-2000:]!r}")
        try:
            if client.ping(timeout=2.0).get("held"):
                return
        except Exception:
            pass
        time.sleep(0.3)
    proc.kill()
    pytest.fail("daemon never reached serving state")


@pytest.fixture(scope="module")
def daemon(tmp_path_factory):
    """Real CPU-kernel daemon (f32 ladder) — the parity oracle's peer."""
    sock = str(tmp_path_factory.mktemp("devd-stream") / "devd.sock")
    proc = _spawn(sock, {"TENDERMINT_DEVD_WARM": "16"})
    client = devd.DevdClient(sock)
    _wait_held(client, proc, 240.0)  # cold .jax_cache: one f32 compile
    yield sock, client
    try:
        client.shutdown()
    except Exception:
        pass
    client.close()
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()


@pytest.fixture()
def sim_daemon(tmp_path):
    """Sim-device daemon: pure-python, holds immediately, device time is
    deterministic (1 ms per 100 lanes at the rate below)."""
    sock = str(tmp_path / "sim.sock")
    proc = _spawn(sock, {"TENDERMINT_DEVD_SIM_RATE": "100000"})
    client = devd.DevdClient(sock)
    _wait_held(client, proc, 30.0)
    yield sock, client, proc
    try:
        client.shutdown()
    except Exception:
        pass
    client.close()
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()


def _items(n: int, tag: bytes = b"stream"):
    seeds = [bytes([9, k]) + b"\x09" * 30 for k in range(8)]
    out = []
    for i in range(n):
        seed = seeds[i % 8]
        msg = tag + b"-%d" % i
        out.append((ed.public_key(seed), msg, ed.sign(seed, msg)))
    return out


def test_streamed_parity_with_single_shot_and_cpu(daemon):
    """Lane-for-lane: streamed == single-shot == _cpu_verify_batch,
    on a batch mixing valid lanes, forged sigs, tampered msgs, and
    msg lengths from 0 to 300 bytes."""
    from tendermint_tpu.ops.gateway import _cpu_verify_batch

    _, client = daemon
    items = _items(37)
    items[3] = (items[3][0], items[3][1], b"\x44" * 64)           # forged
    items[11] = (items[11][0], items[11][1] + b"x", items[11][2])  # tampered
    seed = bytes([9, 0]) + b"\x09" * 30
    items[20] = (ed.public_key(seed), b"", ed.sign(seed, b""))     # empty msg
    long = b"L" * 300
    items[30] = (ed.public_key(seed), long, ed.sign(seed, long))
    items[31] = (items[31][0][::-1], items[31][1], items[31][2])   # wrong key

    want = _cpu_verify_batch(items)
    single = client.verify_batch(items)
    for width in (5, 16, 37, 64):  # remainder, divisor, exact, oversize
        streamed = client.verify_stream(items, chunk=width)
        assert streamed == single == want, f"chunk width {width}"
    assert not all(want)  # the forged lanes actually exercised rejection


def test_streamed_forged_lanes_at_and_across_a_chunk_boundary(daemon):
    """A streamed batch whose forged lanes sit at a chunk's edges (the
    last lane of one chunk and the first of the next, each between valid
    lanes) and inside a chunk: every lane's verdict is plain Ed25519's
    (perfbench/reference/ed25519_ref.py), chunk by chunk."""
    sys.path.insert(0, os.path.join(REPO, "perfbench"))
    from reference import ed25519_ref

    _, client = daemon
    items = _items(48, tag=b"edge")
    for k in (15, 16, 31, 40):       # 16-lane chunks: 15|16 across, 31 at
        pub, msg, sig = items[k]
        if k % 2:
            sig = bytes([sig[0] ^ 0x01]) + sig[1:]
        else:
            msg = msg + b"!"
        items[k] = (pub, msg, sig)
    want = [ed25519_ref.verify(pub, msg, sig) for pub, msg, sig in items]
    assert [k for k, ok in enumerate(want) if not ok] == [15, 16, 31, 40]
    assert client.verify_stream(items, chunk=16) == want


def test_streamed_empty_and_single_item(daemon):
    _, client = daemon
    assert client.verify_stream([]) == []
    one = _items(1, tag=b"one")
    assert client.verify_stream(one, chunk=16) == [True]
    forged = [(one[0][0], one[0][1], b"\x21" * 64)]
    assert client.verify_stream(forged, chunk=16) == [False]


def test_gateway_devd_backend_streams_wide_batches(daemon, monkeypatch):
    """A default-constructed Verifier against a serving daemon routes
    wide batches over the STREAMED transport: daemon-side stream
    counters move and the verifier's stats() carries the client-side
    stream section."""
    sock, client = daemon
    monkeypatch.setenv("TENDERMINT_DEVD_SOCK", sock)
    monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
    monkeypatch.setenv("TENDERMINT_DEVD_STREAM_MIN", "8")
    monkeypatch.setenv("TENDERMINT_DEVD_CHUNK", "16")
    import tendermint_tpu.ops.devd_backend as backend
    from tendermint_tpu.ops import gateway

    monkeypatch.setattr(backend, "_client", None)
    monkeypatch.setattr(backend, "_stream_ok", True)
    devd.bust_avail_cache()
    v = gateway.Verifier(min_tpu_batch=1)
    assert v._kernel == "devd"

    before = client.status()["stream"]
    items = _items(40, tag=b"gw-stream")
    items[7] = (items[7][0], items[7][1], b"\x66" * 64)
    assert v.verify_batch(items) == [i != 7 for i in range(40)]
    after = client.status()["stream"]
    assert after["chunks"] - before["chunks"] == 3  # 40 lanes / width 16
    assert after["lanes"] - before["lanes"] == 40
    assert after["bytes_framed"] > before["bytes_framed"]
    vstats = v.stats()
    assert vstats["tpu_sigs"] == 40
    # flat numeric keys: the metrics RPC exports these as scalar gauges
    assert vstats["stream_lanes"] >= 40
    assert all(isinstance(val, (int, float)) for val in vstats.values())

    # async form too: resolver contract preserved over the stream
    resolve = v.verify_batch_async(items)
    assert resolve() == [i != 7 for i in range(40)]


def test_daemon_overlaps_chunks_in_flight(sim_daemon):
    """The pipelining claim itself: with device time 10 ms/chunk, the
    daemon must be holding multiple dispatched-unresolved chunks at once
    — inflight_max >= 2 — and per-chunk device latency must be
    recorded."""
    _, client, _ = sim_daemon
    items = [(b"\x05" * 32, b"lap-%04d" % i, b"\x06" * 64) for i in range(8000)]
    assert all(client.verify_stream(items, chunk=1000))
    stream = client.status()["stream"]
    assert stream["inflight_max"] >= 2, stream
    assert stream["inflight"] == 0, stream  # all resolved at stream end
    assert stream["chunks"] == 8
    assert stream["chunk_device_ms_last"] > 0
    assert stream["chunk_device_ms_avg"] > 0


def test_malformed_mid_stream_frame_gets_error_frame(sim_daemon):
    """Speak the raw protocol: one good chunk, then garbage. The daemon
    must answer the good chunk, send an ERROR frame for the bad one
    (never hang), and close the stream."""
    sock, _, _ = sim_daemon
    conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    conn.settimeout(10.0)
    conn.connect(sock)
    try:
        devd._send_frame(conn, {"op": "verify_stream", "chunks": 3, "total": 8})
        good = devd._pack_chunk(
            [(b"\x07" * 32, b"mal-%d" % i, b"\x08" * 64) for i in range(4)]
        )
        conn.sendall(struct.pack(">I", len(good)) + good)
        garbage = b"\xde\xad\xbe\xef" * 5  # claims 0xefbeadde lanes
        conn.sendall(struct.pack(">I", len(garbage)) + garbage)

        first = devd._recv_raw_frame(conn)
        status, idx = struct.unpack_from("<BI", first, 0)
        assert (status, idx) == (devd.STREAM_OK, 0)
        second = devd._recv_raw_frame(conn)
        status, idx = struct.unpack_from("<BI", second, 0)
        assert status == devd.STREAM_ERR and idx == 1
        assert b"malformed" in second[5:]
        # stream aborted: the daemon closes rather than guess at framing
        conn.settimeout(5.0)
        assert conn.recv(1) == b""
    finally:
        conn.close()


def test_malformed_stream_leaves_daemon_serving(sim_daemon):
    """After an aborted stream the daemon still serves new connections,
    and the error counter moved."""
    sock, client, _ = sim_daemon
    bad = devd.DevdClient(sock)
    with pytest.raises(devd.DevdError, match="malformed|mismatch"):
        # undersized chunk: daemon's size validation rejects it
        conn, _ = bad._acquire()
        devd._send_frame(conn, {"op": "verify_stream", "chunks": 1, "total": 4})
        conn.sendall(struct.pack(">I", 2) + b"\x01\x02")
        bad._collect_stream(conn, _NopThread(), [], 1)
    bad.close()
    # the daemon counts the abort on ITS side of the torn stream — poll
    # briefly: under a loaded suite the error handling can land after
    # the client's exception (the status read raced it)
    deadline = time.monotonic() + 5.0
    while client.status()["stream"]["errors"] < 1 and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    rep = client.status()
    assert rep["stream"]["errors"] >= 1
    assert all(client.verify_stream(
        [(b"\x05" * 32, b"after-%d" % i, b"\x06" * 64) for i in range(6)],
        chunk=4,
    ))


class _NopThread:
    def join(self, timeout=None):
        pass


def test_bad_lane_fails_fast_without_hanging(sim_daemon):
    """A malformed lane kills the writer mid-stream; the client must
    surface the ValueError promptly (no io_timeout hang, no retry of a
    deterministic failure) and the daemon must keep serving."""
    _, client, _ = sim_daemon
    items = [(b"\x05" * 32, b"bl-%d" % i, b"\x06" * 64) for i in range(10)]
    items[7] = (b"short", items[7][1], items[7][2])
    t0 = time.time()
    with pytest.raises(ValueError, match="route non-ed25519"):
        client.verify_stream(items, chunk=4)
    assert time.time() - t0 < 10.0  # failed fast, not at io_timeout
    good = [(b"\x05" * 32, b"ok-%d" % i, b"\x06" * 64) for i in range(6)]
    assert all(client.verify_stream(good, chunk=4))


def test_client_reconnects_after_daemon_restart(tmp_path):
    """Pooled connections go stale when the daemon restarts; the next
    request (single-shot AND streamed) must retry on a fresh socket with
    no caller-visible flap."""
    sock = str(tmp_path / "restart.sock")
    proc = _spawn(sock, {"TENDERMINT_DEVD_SIM_RATE": "100000"})
    client = devd.DevdClient(sock)
    _wait_held(client, proc, 30.0)
    items = [(b"\x05" * 32, b"rc-%d" % i, b"\x06" * 64) for i in range(32)]
    assert all(client.verify_stream(items, chunk=8))
    assert all(client.verify_batch(items))

    client.shutdown()
    proc.wait(timeout=15)
    proc2 = _spawn(sock, {"TENDERMINT_DEVD_SIM_RATE": "100000"})
    try:
        _wait_held(devd.DevdClient(sock), proc2, 30.0)
        # same client object, pool full of dead sockets from daemon #1
        assert all(client.verify_stream(items, chunk=8))
        assert all(client.verify_batch(items))
        assert client.stream_stats()["reconnects"] >= 1
    finally:
        try:
            client.shutdown()
        except Exception:
            pass
        client.close()
        try:
            proc2.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc2.kill()


def test_status_op_exposes_stream_counters(sim_daemon):
    _, client, _ = sim_daemon
    rep = client.status()
    assert rep["ok"] and rep["held"]
    assert {"chunks", "lanes", "bytes_framed", "inflight", "inflight_max",
            "errors", "chunk_device_ms_last"} <= set(rep["stream"])
    assert rep["stream_chunk"] >= 1
    assert rep["stream_depth"] >= 2
    # plain stats op carries the same section
    full = client.request({"op": "stats"})
    assert full["ok"] and "stream" in full
