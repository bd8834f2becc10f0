"""The daemon's per-call records (tendermint_tpu/devd_spans.py), taken
from inside: five phases per verifier call, the ring, the `spans` and
`profile` ops, the dump at stop, and the client's side of the same call
(`svc_ns`, `devd_single_shot_ipc_seconds`).

Behaviour rides the sim-device daemon (no jax, instant start); the
profiler needs a real CPU-kernel daemon (the one the stream tests start
the same way). Guards at the end pin what the benchmark hangs on.
"""

from __future__ import annotations

import glob
import json
import os
import pickle
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from tendermint_tpu import devd, devd_spans
from tendermint_tpu.crypto import ed25519 as ed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = ("t_recv0", "t_decoded", "t_marshalled", "t_dispatched", "t_verdicts",
     "t_replied")


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
        env=env, cwd=REPO, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
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
        time.sleep(0.2)
    proc.kill()
    pytest.fail("daemon never reached serving state")


def _stop(client, proc) -> None:
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
def short_dir():
    """A directory for a unix socket: tmp_path under xdist, with these
    tests' names in it, passes the 107 bytes a socket's path may have."""
    d = tempfile.mkdtemp(prefix="cr-")
    yield d
    shutil.rmtree(d, ignore_errors=True)


@pytest.fixture()
def sim(short_dir):
    """Sim-device daemon at 2000 lanes/s: a 100-lane call holds the
    'device' 50 ms, long enough for two clients to overlap."""
    sock = os.path.join(short_dir, "sim.sock")
    proc = _spawn(sock, {"TENDERMINT_DEVD_SIM_RATE": "2000"})
    client = devd.DevdClient(sock)
    _wait_held(client, proc, 30.0)
    yield sock, client, proc
    _stop(client, proc)


@pytest.fixture(scope="module")
def cpu_daemon():
    """Real CPU-kernel daemon (f32 ladder): the one process of these
    tests whose jax can hold a profiler trace."""
    home = tempfile.mkdtemp(prefix="cr-")
    sock = os.path.join(home, "devd.sock")
    proc = _spawn(sock, {"TENDERMINT_DEVD_WARM": "8"})
    client = devd.DevdClient(sock)
    _wait_held(client, proc, 240.0)
    yield sock, client
    _stop(client, proc)
    shutil.rmtree(home, ignore_errors=True)


def _items(n: int, tag: bytes = b"rec"):
    seeds = [bytes([7, k]) + b"\x07" * 30 for k in range(4)]
    return [(ed.public_key(seeds[i % 4]), tag + b"-%d" % i,
             ed.sign(seeds[i % 4], tag + b"-%d" % i)) for i in range(n)]


def _records(client, **kw) -> list[dict]:
    rep = client.spans(**kw)
    assert tuple(rep["fields"]) == devd_spans.FIELDS
    return [dict(zip(rep["fields"], r)) for r in rep["records"]]


# -- records ------------------------------------------------------------------


def test_instants_monotone_and_phases_sum_exactly(sim):
    _, client, _ = sim
    assert client.verify_batch(_items(3)) == [True] * 3
    with devd.asking("sync"):
        assert client.verify_stream(_items(20), chunk=8) == [True] * 20
    recs = _records(client)
    assert [(r["op"], r["lanes"]) for r in recs] == [
        ("verify", 3), ("verify_stream", 8), ("verify_stream", 8),
        ("verify_stream", 4)]
    for r in recs:
        ts = [r[k] for k in T]
        assert ts == sorted(ts), r
        phases = [ts[i + 1] - ts[i] for i in range(5)]
        assert sum(phases) == r["t_replied"] - r["t_recv0"]
        assert all(p >= 0 for p in phases)
        # the sim device's time is its wait: 0.5 ms a lane at this rate
        assert r["t_verdicts"] - r["t_dispatched"] >= r["lanes"] * 400_000
    # who asked and why: this process, named as its client names it;
    # a call no site named is set-up traffic, and a stream's chunks
    # carry the stream's rid
    assert recs[0]["rid"].startswith(f"{devd._client_name}-warm-")
    assert (recs[0]["node"], recs[0]["why"]) == (devd._client_name, "warm")
    assert len({r["rid"] for r in recs[1:]}) == 1
    assert {(r["node"], r["why"]) for r in recs[1:]} == {
        (devd._client_name, "sync")}
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(set(seqs))
    assert len({r["conn"] for r in recs[1:]}) == 1


def test_in_flight_at_recv_under_two_concurrent_clients(sim):
    sock, client, _ = sim
    other = devd.DevdClient(sock)
    batch = _items(100)
    threads = [threading.Thread(target=c.verify_batch, args=(batch,))
               for c in (client, other)]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join()
    other.close()
    recs = _records(client)
    assert len(recs) == 2
    assert sorted(r["in_flight_at_recv"] for r in recs) == [0, 1]
    assert client.status()["spans"]["open"] == 0


def test_ring_wraps_and_the_header_says_so(tmp_path):
    ring = devd_spans.SpanRing(size=4)
    for i in range(6):
        rec = ring.begin(conn=1)
        ring.decoded(rec, "verify", i + 1)
        ring.finish(rec)
    rows = ring.rows()
    assert [r[3] for r in rows] == [3, 4, 5, 6]   # lanes of the newest four
    assert ring.stats() == {"size": 4, "count": 6, "open": 0}
    path = ring.dump(str(tmp_path / "x.spans.jsonl"), pid=1)
    lines = open(path).read().splitlines()
    head = json.loads(lines[0])
    assert head["count"] == 6 > head["ring_size"] == 4
    assert head["records"] == 4 == len(lines) - 1
    assert head["fields"] == list(devd_spans.FIELDS)


def test_ring_under_more_writers_than_cores_loses_nothing():
    """Handler threads share the ring: every record lands, every seq is
    given once, and the count of open requests comes back to 0."""
    ring = devd_spans.SpanRing(size=1 << 15)
    workers, each = 4 * (os.cpu_count() or 2), 400
    seen_open = []

    def serve(conn):
        for i in range(each):
            rec = ring.begin(conn)
            ring.decoded(rec, "verify", 1, f"{conn}-{i}")
            devd_spans.mark("marshal", 8)
            seen_open.append(ring.open)
            ring.finish(rec)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=serve, args=(c,))
                   for c in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    rows = ring.rows()
    assert ring.count == len(rows) == workers * each
    assert ring.open == 0 and max(seen_open) <= workers
    assert len({r[0] for r in rows}) == len(rows)          # seq
    assert len({r[12] for r in rows}) == len(rows)         # rid
    assert all(r[4] == 8 and r[5] <= r[10] for r in rows)  # width; instants


def test_dump_appears_at_shutdown_and_parses(sim):
    sock, client, proc = sim
    client.verify_batch(_items(2))
    client.verify_batch(_items(5))
    path = devd_spans.dump_path(sock)
    assert path == sock[:-len(".sock")] + ".spans.jsonl"
    assert not os.path.exists(path)
    client.shutdown()
    assert proc.wait(timeout=15) == 0
    lines = open(path).read().splitlines()
    head = json.loads(lines[0])
    assert head["pid"] == proc.pid and head["count"] == 2
    assert head["ring_size"] == devd_spans.RING_SIZE == 65536
    assert "device_kind" in head
    rows = [dict(zip(head["fields"], json.loads(x))) for x in lines[1:]]
    assert [r["lanes"] for r in rows] == [2, 5]
    assert all(r["t_replied"] >= r["t_recv0"] for r in rows)


def test_dump_appears_on_sigterm_under_exit_on_term(sim):
    sock, client, proc = sim
    client.verify_batch(_items(1))
    proc.send_signal(signal.SIGTERM)
    assert proc.wait(timeout=15) == 0
    head = json.loads(open(devd_spans.dump_path(sock)).readline())
    assert head["count"] == 1
    assert not os.path.exists(sock)


def test_spans_since_ns_and_last(sim):
    _, client, _ = sim
    client.verify_batch(_items(1))
    cut = time.time_ns()
    client.verify_batch(_items(2))
    client.verify_batch(_items(3))
    assert [r["lanes"] for r in _records(client)] == [1, 2, 3]
    assert [r["lanes"] for r in _records(client, since_ns=cut)] == [2, 3]
    assert [r["lanes"] for r in _records(client, last=1)] == [3]
    assert [r["lanes"] for r in _records(client, since_ns=cut, last=5)] == [2, 3]
    rep = client.spans(last=0)
    assert rep["records"] == [] and rep["count"] == 3 and rep["size"] == 65536


def test_status_reports_the_ring(sim):
    _, client, _ = sim
    assert client.status()["spans"] == {"size": 65536, "count": 0, "open": 0}
    client.verify_batch(_items(1))
    st = client.status()
    assert st["spans"]["count"] == 1 and st["profiling"] is False
    assert "spans" not in client.ping()   # ping stays small


def test_ping_status_and_failed_calls_leave_no_record(sim):
    _, client, _ = sim
    client.ping()
    client.status()
    client.hash_batch([b"abc"])
    rep = client.request({"op": "verify"})  # no items: KeyError in the daemon
    assert rep["ok"] is False and "KeyError" in rep["error"]
    st = client.status()["spans"]
    assert st["count"] == 0 and st["open"] == 0


# -- the client's side of the same call ----------------------------------------


def test_reply_carries_svc_ns_and_the_ipc_is_observed(sim):
    from tendermint_tpu.libs import telemetry

    _, client, _ = sim
    rep = client.request({"op": "verify", "items": _items(10), "rid": "me-1"},
                         timeout=30)
    assert rep["ok"] and rep["svc_ns"] >= 10 * 400_000
    rec = _records(client)[-1]
    assert rec["rid"] == "me-1"
    # svc_ns ends before the reply is encoded: inside the record's span
    assert rec["t_verdicts"] - rec["t_recv0"] <= rep["svc_ns"] \
        <= rec["t_replied"] - rec["t_recv0"]

    reg = telemetry.default_registry()
    before = devd.thread_ipc_ns()
    t0 = time.perf_counter()
    client.verify_batch(_items(10))
    rtt = time.perf_counter() - t0
    gained = (devd.thread_ipc_ns() - before) / 1e9
    assert 0 < gained < rtt
    text = reg.render_prometheus()
    line = next(x for x in text.splitlines() if x.startswith(
        'devd_single_shot_ipc_seconds_count{op="verify"}'))
    assert float(line.rsplit(" ", 1)[1]) >= 1
    # another thread's calls are not this thread's IPC
    seen = []
    th = threading.Thread(target=lambda: seen.append(devd.thread_ipc_ns()))
    th.start()
    th.join()
    assert seen == [0]


def test_old_daemon_reply_without_svc_ns_is_tolerated(short_dir):
    """A daemon from before the records answers without `svc_ns` (and
    ignores `rid`): the round trip is still observed, the IPC is not."""
    from tendermint_tpu.libs import telemetry

    path = os.path.join(short_dir, "old.sock")
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(path)
    srv.listen(1)
    got = []

    def old_daemon():
        conn, _ = srv.accept()
        (n,) = struct.unpack(">I", devd._recv_exact(conn, 4))
        req = pickle.loads(devd._recv_exact(conn, n))
        got.append(req)
        devd._send_frame(conn, {"ok": True,
                                "results": [True] * len(req["items"])})
        conn.close()

    th = threading.Thread(target=old_daemon, daemon=True)
    th.start()
    reg = telemetry.default_registry()

    def count(name):
        for x in reg.render_prometheus().splitlines():
            if x.startswith(f'{name}_count{{op="verify"}}'):
                return float(x.rsplit(" ", 1)[1])
        return 0.0

    single0, ipc0 = count("devd_single_shot_seconds"), \
        count("devd_single_shot_ipc_seconds")
    before = devd.thread_ipc_ns()
    c = devd.DevdClient(path)
    assert c.verify_batch(_items(2)) == [True, True]
    c.close()
    th.join(5)
    srv.close()
    assert got[0]["rid"].startswith(f"{devd._client_name}-warm-")
    assert count("devd_single_shot_seconds") == single0 + 1
    assert count("devd_single_shot_ipc_seconds") == ipc0
    assert devd.thread_ipc_ns() == before


def test_old_client_without_rid_is_served(sim):
    _, client, _ = sim
    rep = client.request({"op": "verify", "items": _items(1)}, timeout=30)
    assert rep["ok"] and rep["results"] == [True]
    assert _records(client)[-1]["rid"] == ""


def test_bench_op_is_gone(sim):
    _, client, _ = sim
    rep = client.request({"op": "bench", "batch": 8, "n_batches": 1})
    assert rep["ok"] is False and "unknown op" in rep["error"]
    assert not hasattr(devd.DevdClient, "bench")
    assert not hasattr(devd, "_bench_gate")


# -- the profiler, from inside ---------------------------------------------------


def _xplane_events(tdir: str) -> list[tuple[str, dict]]:
    from jax.profiler import ProfileData

    hits = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    assert len(hits) == 1, hits
    out = []
    for plane in ProfileData.from_file(hits[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("devd."):
                    out.append((ev.name, dict(ev.stats)))
    return out


def test_profile_start_stop_leaves_annotated_xplane(cpu_daemon, tmp_path):
    _, client = cpu_daemon
    tdir = str(tmp_path / "trace")
    a = client.profile_start(tdir)
    assert a["ok"] and a["start_wall_ns"] > 0
    assert client.status()["profiling"] is True
    client.verify_batch(_items(2, b"p1"))
    client.verify_batch(_items(3, b"p2"))
    assert client.verify_stream(_items(8, b"p3"), chunk=8) == [True] * 8
    b = client.profile_stop()
    assert b["traced_calls"] == 3 and b["stop_trace_s"] > 0
    assert b["stop_wall_ns"] > a["start_wall_ns"]
    assert client.status()["profiling"] is False
    recs = _records(client, since_ns=a["start_wall_ns"])
    assert [r["lanes"] for r in recs] == [2, 3, 8]
    events = _xplane_events(tdir)
    clocks = sorted(int(n.split(":")[1]) for n, _s in events
                    if n.startswith("devd.clock:"))
    assert clocks == [a["start_wall_ns"], b["stop_wall_ns"]]
    for r in recs:
        mine = sorted(n for n, s in events if s.get("seq") == r["seq"])
        assert mine == sorted("devd." + p for p in devd_spans.PHASES), (r, mine)
        lanes = {s.get("lanes") for n, s in events
                 if s.get("seq") == r["seq"] and n != "devd.decode"}
        assert lanes == {r["lanes"]}
    # the kernel's own marks, not the daemon's fallbacks: a width, and
    # three phases of more than nothing
    for r in recs:
        assert r["width"] == 8
        assert r["t_marshalled"] > r["t_decoded"]
        assert r["t_dispatched"] > r["t_marshalled"]
        assert r["t_verdicts"] > r["t_dispatched"]


def test_profile_second_start_is_refused_by_an_error_reply(cpu_daemon, tmp_path):
    _, client = cpu_daemon
    client.profile_start(str(tmp_path / "one"))
    try:
        rep = client.request({"op": "profile", "action": "start",
                              "dir": str(tmp_path / "two")})
        assert rep == {"ok": False, "error": "a profile is already running"}
        with pytest.raises(devd.DevdError, match="already running"):
            client.profile_start(str(tmp_path / "two"))
        assert client.verify_batch(_items(1, b"still")) == [True]
    finally:
        client.profile_stop()
    assert not os.path.exists(str(tmp_path / "two"))
    rep = client.request({"op": "profile", "action": "sideways"})
    assert rep["ok"] is False and "bad request" in rep["error"]


def test_profile_max_calls_stops_it_without_holding_the_call(cpu_daemon, tmp_path):
    _, client = cpu_daemon
    tdir = str(tmp_path / "bounded")
    client.profile_start(tdir, max_calls=2)
    client.verify_batch(_items(1, b"m1"))
    t0 = time.perf_counter()
    client.verify_batch(_items(1, b"m2"))       # trips the bound
    tripped_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    client.verify_batch(_items(1, b"m3"))       # served while it writes
    after_s = time.perf_counter() - t0
    rep = client.profile_stop()                  # the first stop's answer
    assert rep["ok"] and rep["traced_calls"] == 2
    assert client.status()["profiling"] is False
    # writing the trace out takes whole seconds even here; the call that
    # tripped the bound and the next one were not held for it
    assert tripped_s < rep["stop_trace_s"] and after_s < rep["stop_trace_s"]
    assert rep["stop_trace_s"] > 3 * tripped_s
    assert glob.glob(os.path.join(tdir, "plugins", "profile", "*", "*.xplane.pb"))
    again = client.profile_stop()
    assert again == rep


def test_profile_on_a_daemon_without_jax_is_an_error_reply(sim):
    _, client, _ = sim
    rep = client.request({"op": "profile", "action": "start", "dir": "/tmp/x"})
    assert rep == {"ok": False, "error": "this daemon runs no jax"}
    rep = client.request({"op": "profile", "action": "stop"})
    assert rep == {"ok": False, "error": "no profile was started"}
    assert client.verify_batch(_items(1)) == [True]


# -- mark() outside the daemon ----------------------------------------------------


class _CountingTime:
    def __init__(self):
        self.reads = 0

    def time_ns(self):
        self.reads += 1
        return time.time_ns()

    def __getattr__(self, name):
        return getattr(time, name)


def test_mark_with_no_record_open_reads_no_clock(monkeypatch):
    clock = _CountingTime()
    monkeypatch.setattr(devd_spans, "time", clock)
    devd_spans.attach(None)
    for phase in devd_spans.PHASES:
        devd_spans.mark(phase, 8)
    assert clock.reads == 0
    # a kernel call in a process that is not the daemon: same thing
    from tendermint_tpu.ops import ed25519_f32

    assert list(ed25519_f32.verify_batch(_items(2))) == [True, True]
    assert clock.reads == 0
    # and with one open it reads one clock a mark, first mark wins
    ring = devd_spans.SpanRing(size=2)
    rec = ring.begin(conn=1)
    assert clock.reads == 1
    ring.decoded(rec, "verify", 1)
    devd_spans.mark("marshal", 8)
    devd_spans.mark("marshal", 16)   # ignored: the phase has ended
    assert clock.reads == 3 and rec.width == 8
    devd_spans.mark("device_wait")   # dispatch skipped: empty, at this instant
    ring.finish(rec)
    row = dict(zip(devd_spans.FIELDS, ring.rows()[0]))
    assert row["t_dispatched"] == row["t_verdicts"] > row["t_marshalled"]
    assert clock.reads == 5
    devd_spans.mark("reply")
    assert clock.reads == 5   # finish() closed it: no record is open


@pytest.mark.parametrize("rid, node, why", [
    ("node3-gate-17", "node3", "gate"),
    ("node-a-3-vote-2", "node-a-3", "vote"),   # a moniker with dashes
    ("p4242-warm-1", "p4242", "warm"),
    ("4242-17", "", ""),                       # an older client's rid
    ("", "", ""),
])
def test_record_names_node_and_why_from_the_rid(rid, node, why):
    ring = devd_spans.SpanRing(size=2)
    rec = ring.begin(conn=1)
    ring.decoded(rec, "verify", 1, rid)
    ring.finish(rec)
    row = dict(zip(devd_spans.FIELDS, ring.rows()[0]))
    assert (row["rid"], row["node"], row["why"]) == (rid, node, why)


def test_client_rid_names_the_process_and_the_callers_purpose(monkeypatch):
    monkeypatch.setattr(devd, "_client_name", "node5")
    devd.take_rid()
    with devd.asking("commit"):
        rid = devd._next_rid()
        with devd.asking("block"):
            inner = devd._next_rid()
        assert devd.current_why() == "commit"
    outside = devd._next_rid()
    assert devd_spans.parse_rid(rid)[0] == "node5"
    assert [devd_spans.parse_rid(r)[1] for r in (rid, inner, outside)] == [
        "commit", "block", "warm"]
    assert devd.take_rid() == outside and devd.take_rid() == ""


# -- what the benchmark hangs on ----------------------------------------------------


def test_class_property_in_place_of_verifier_sees_the_claims_assignment(monkeypatch):
    """perfbench's launcher replaces `_DaemonState.verifier` with a class
    property to wrap the verifier the claim hands over: the attribute is
    assigned once, by the claim, on an instance."""
    seen = []

    def get(self):
        return self.__dict__.get("_held")

    def set_(self, v):
        seen.append(v)
        self.__dict__["_held"] = v

    monkeypatch.setattr(devd._DaemonState, "verifier", property(get, set_),
                        raising=False)
    monkeypatch.setenv("TENDERMINT_DEVD_SIM_RATE", "1000")
    st = devd._DaemonState()
    assert seen == [None]          # __init__'s own
    devd._device_loop(st, accept_cpu=True, warm_shapes=())
    assert len(seen) == 2 and seen[1] is st.verifier
    assert hasattr(st.verifier, "verify_batch_async")
    assert hasattr(st.verifier, "verify_batch")
    assert st.status == "serving"


def test_handlers_call_verify_batch_and_verify_batch_async_on_the_attribute():
    """The launcher wraps exactly these two entry points."""
    import inspect

    # a single-shot `verify` goes through the merger, which reads the
    # state's `verifier` attribute anew for every program it runs
    assert "st.merger.verify(items" in inspect.getsource(devd._handle_conn)
    src = inspect.getsource(devd._VerifyMerger._run)
    assert "self._st.verifier" in src and "v.verify_batch(items)" in src
    assert "v.verify_batch_async" in inspect.getsource(devd._handle_verify_stream)


def test_lowered_name_of_the_comb_kernel_holds_verify_comb_impl():
    """Three metrics find the kernel's device events by
    `jit__verify_comb_impl`."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519_comb as comb
    from tendermint_tpu.ops import ed25519_f32 as base

    pool = comb.CombPool(capacity=8)
    spec = jax.ShapeDtypeStruct
    _ax, _ay, ry, rs, s8, h8, _valid = base.prepare_batch8([], 8)
    args = (spec(pool._pool.shape, pool._pool.dtype),
            spec(pool.table_b().shape, pool.table_b().dtype),
            spec((8,), jnp.int32),
            *(spec(a.shape, jnp.asarray(a).dtype) for a in (ry, rs, s8, h8)))
    text = comb._verify_jit.lower(*args).as_text()
    assert "module @jit__verify_comb_impl" in text


def test_the_profiler_is_started_only_by_the_profile_op():
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "tendermint_tpu")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(root, f)).read()
                if "profiler.start_trace" in text or "start_trace(" in text:
                    hits.append(os.path.relpath(os.path.join(root, f), REPO))
    assert hits == ["tendermint_tpu/devd_spans.py"]
    assert '"bench"' not in open(os.path.join(REPO, "tendermint_tpu",
                                              "devd.py")).read()
