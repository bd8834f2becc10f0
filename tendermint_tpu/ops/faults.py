"""Injectable fault harness for the devd device plane (round 8).

The consensus critical path now runs through a socket to a separate
daemon process (PR 1 verify plane, PR 2 hash plane) — which means the
failure modes that matter are TRANSPORT failure modes: a daemon killed
mid-stream, a truncated or corrupted chunk frame, a read that stalls
until the io budget, a refused connect, a version-skewed daemon. Before
this module the only way to exercise any of them was hand-killing
daemons. A `FaultPlan` is a DETERMINISTIC, seeded schedule of such
faults that tests and benches inject WITHOUT monkeypatching client or
daemon internals, deployed either of two ways:

- **in-process** (`install_client_faults`): wraps every new DevdClient
  connection via the sanctioned `devd.set_socket_wrapper` hook — the
  production client code path runs unmodified, faults fire at the
  socket boundary (sendall/recv). Cheap, runs anywhere, covers the
  client-side triage (reconnect-once, breaker demotion, CPU fallback).
- **out-of-process** (`FaultProxy`): a UDS shim process/thread in front
  of a REAL daemon. The client speaks the real wire protocol to the
  proxy; every length-prefixed frame relays byte-for-byte unless the
  plan injects — so `verify_stream`/`hash_stream` framing, the daemon's
  malformed-frame error path, and the daemon-side abort handling are
  exercised on real bytes. `python -m tendermint_tpu.ops.faults` runs
  it as its own process for multi-process harnesses (localnet).

Every injected fault increments a `faults_*` counter; registered plans
surface those counters alongside the existing `stream_*` gauges in
`Verifier.stats()` / `Hasher.stats()` (flat numerics — the metrics RPC
exports them as scalar gauges), so a chaos run's observability is the
SAME observability an operator has in production.

`DaemonSupervisor` drives the kill/restart arm of a chaos schedule. It
is chip-free BY CONSTRUCTION: it refuses to supervise anything but an
ACCEPT_CPU (sim or CPU-kernel) daemon — a real device owner is shut
down through its `shutdown` op, and a SIGKILLed one can leave libtpu's
lock behind for the next claim; no test harness may automate that.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import pickle
import random
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time

logger = logging.getLogger("ops.faults")

# The fault taxonomy (docs/streaming-devd.md "Failure model"):
#   refuse    connect refused (daemon down / socket gone)
#   corrupt   byte flip inside a relayed frame payload (framing intact)
#   truncate  frame cut mid-payload, connection closed (framing broken)
#   stall     read/write stalled for stall_s before proceeding
#   drop      connection closed without warning mid-exchange
#   skew      a *_stream header answered like a pre-streaming daemon
#             (pickle {"ok": False}) — the version-skew path
#   kill      daemon killed/restarted (DaemonSupervisor / blackout)
FAULT_KINDS = ("refuse", "corrupt", "truncate", "stall", "drop", "skew", "kill")

# plan event streams a Fault can key on: "connect" (new client conn),
# "c2s" (client->daemon frame), "s2c" (daemon->client frame)
FAULT_EVENTS = ("connect", "c2s", "s2c")


class Fault:
    """One rule in a FaultPlan: fire `kind` on the `first`-th event of
    stream `on` (1-based), then every `every` events after, at most
    `limit` times total. Deterministic by construction — the schedule is
    a pure function of the event sequence."""

    __slots__ = ("kind", "on", "first", "every", "limit", "stall_s", "fired")

    def __init__(self, kind: str, on: str, first: int = 1, every: int = 0,
                 limit: int = 1, stall_s: float = 0.5):
        if kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {kind!r}: {FAULT_KINDS}")
        if on not in FAULT_EVENTS:
            raise ValueError(f"unknown fault event {on!r}: {FAULT_EVENTS}")
        self.kind = kind
        self.on = on
        self.first = max(1, int(first))
        self.every = max(0, int(every))
        self.limit = max(1, int(limit))
        self.stall_s = float(stall_s)
        self.fired = 0

    def due(self, n: int) -> bool:
        if self.fired >= self.limit:
            return False
        if n == self.first:
            return True
        return bool(self.every) and n > self.first and (
            (n - self.first) % self.every == 0
        )

    def __repr__(self) -> str:  # schedule debugging in test failures
        return (
            f"Fault({self.kind} on {self.on} first={self.first} "
            f"every={self.every} limit={self.limit} fired={self.fired})"
        )


class FaultPlan:
    """A seeded, deterministic schedule of device-plane faults plus the
    counters proving what actually fired. The seed drives only the
    *content* randomness (which byte a corrupt flips); *when* faults
    fire is a pure function of the event sequence, so a replayed run
    injects the identical schedule."""

    def __init__(self, faults=(), seed: int = 0):
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self.faults = list(faults)
        self.counters = {f"faults_{k}": 0 for k in FAULT_KINDS}
        self._events = {e: 0 for e in FAULT_EVENTS}
        self._mtx = threading.Lock()

    def add(self, kind: str, on: str, **kw) -> "FaultPlan":
        self.faults.append(Fault(kind, on, **kw))
        return self

    def pick(self, event: str, supported=None):
        """Advance the `event` stream one step; the Fault due at this
        step (counters noted), or None. `supported` (an iterable of
        kinds, None = all) names what the CALLING injection point can
        actually inject here — a due fault it cannot inject is skipped
        WITHOUT being consumed or counted (and warned about once), so
        the faults_* counters only ever report injections that really
        happened and a mis-targeted rule is loud, not silently eaten."""
        with self._mtx:
            self._events[event] += 1
            n = self._events[event]
            for f in self.faults:
                if f.on != event or not f.due(n):
                    continue
                if supported is not None and f.kind not in supported:
                    logger.warning(
                        "fault %r due but not injectable at this point "
                        "(supports %s); skipped, not counted", f,
                        tuple(supported),
                    )
                    continue
                f.fired += 1
                self.counters[f"faults_{f.kind}"] += 1
                return f
        return None

    def wants(self, kind: str, event: str) -> bool:
        """Does any not-yet-exhausted rule target (kind, event)? Lets
        injection points skip per-frame work (e.g. header sniffing for
        skew) when no rule could ever need it."""
        with self._mtx:
            return any(
                f.kind == kind and f.on == event and f.fired < f.limit
                for f in self.faults
            )

    def note(self, kind: str) -> None:
        """Count a fault injected OUTSIDE the event streams (a daemon
        kill by the supervisor, a proxy blackout)."""
        with self._mtx:
            self.counters[f"faults_{kind}"] += 1

    def corrupt_offset(self, lo: int, hi: int) -> int:
        """Seeded byte position for a corrupt fault (content randomness
        is the ONLY thing the rng decides)."""
        with self._mtx:
            return self._rng.randrange(lo, max(lo + 1, hi))

    def stats(self) -> dict:
        with self._mtx:
            out = dict(self.counters)
            out["faults_total"] = sum(self.counters.values())
            return out


# -- registry: stats visibility alongside the stream_* gauges -----------------

_registry: list[FaultPlan] = []
_reg_mtx = threading.Lock()


def register(plan: FaultPlan) -> FaultPlan:
    with _reg_mtx:
        if plan not in _registry:
            _registry.append(plan)
    return plan


def unregister(plan: FaultPlan) -> None:
    with _reg_mtx:
        if plan in _registry:
            _registry.remove(plan)


def global_counters() -> dict:
    """Aggregated faults_* counters over every registered plan — a
    STABLE key set (all zeros with no harness installed), folded into
    Verifier/Hasher stats() so chaos observability is production
    observability."""
    out = {f"faults_{k}": 0 for k in FAULT_KINDS}
    with _reg_mtx:
        plans = list(_registry)
    for plan in plans:
        for k, v in plan.stats().items():
            if k in out:
                out[k] += v
    return out


# -- telemetry plane (round 11) -----------------------------------------------
#
# The fault counters and the supervisor's kill/restart totals register
# into the process-wide telemetry registry, so a chaos soak asserts on
# SCRAPED metrics (GET /metrics, or registry flatten) instead of
# reaching into harness objects — the same surface production has.

_sup_totals = {"kills": 0, "restarts": 0}
_sup_mtx = threading.Lock()


def _note_supervisor(kind: str) -> None:
    with _sup_mtx:
        _sup_totals[kind] += 1


def telemetry_counters() -> dict:
    """faults_* across every registered plan + supervisor churn totals
    (flat numerics; registered as a scrape-only producer below)."""
    out = global_counters()
    with _sup_mtx:
        out["faults_supervisor_kills"] = _sup_totals["kills"]
        out["faults_supervisor_restarts"] = _sup_totals["restarts"]
    return out


def _install_telemetry(reg) -> None:
    # prefix "": the keys already carry the canonical faults_ prefix.
    # legacy=False: scrape-only — the metrics RPC's flat key set must
    # stay byte-compatible (faults_* already ride gateway_verify_* /
    # gateway_hash_* there on the devd route)
    reg.register_producer("", telemetry_counters, legacy=False)


from tendermint_tpu.libs import telemetry as _telemetry  # noqa: E402

_telemetry.on_default_registry(_install_telemetry)


# -- in-process deployment: DevdClient socket wrapper -------------------------


class FaultSocket:
    """Socket proxy injecting plan faults at the client's socket
    boundary. The client sends every frame with ONE sendall (header
    pickle and chunk frames alike), so c2s faults key cleanly on sendall
    calls; s2c faults key on recv calls (the client reads the 4-byte
    length and the payload in separate _recv_exact passes — a corrupt
    may therefore land in either, both of which must surface as a
    client-visible error, never a hang). Everything else delegates to
    the wrapped socket."""

    def __init__(self, sock: socket.socket, plan: FaultPlan):
        self._sock = sock
        self._plan = plan
        # s2c frame tracking: the client reads each frame as a 4-byte
        # length prefix then the payload (possibly in several recv
        # calls). Faults key on FRAMES — fired once, at the first
        # payload read — so the event stream is deterministic (recv
        # call chunking varies run to run) and a corrupt can only ever
        # land in the frame's leading structural bytes, never on a
        # continuation read deep in payload (which would be the silent
        # rot the taxonomy declares out of contract)
        self._len_rem = 4
        self._len_acc = b""
        self._frame_rem = 0
        self._frame_new = False

    # -- fault points -------------------------------------------------------

    def sendall(self, data) -> None:
        f = self._plan.pick(
            "c2s", supported=("stall", "drop", "truncate", "corrupt")
        )
        if f is not None:
            if f.kind == "stall":
                time.sleep(f.stall_s)
            elif f.kind == "drop":
                # shutdown-then-close (_kill_sock): a resolver thread may
                # be blocked in recv on this same fd, and close() alone
                # would leave it wedged for the full stream budget
                _kill_sock(self._sock)
                raise ConnectionError("fault: connection dropped before send")
            elif f.kind == "truncate":
                cut = max(1, len(data) // 2)
                try:
                    self._sock.sendall(bytes(data[:cut]))
                finally:
                    _kill_sock(self._sock)
                raise ConnectionError("fault: frame truncated mid-send")
            elif f.kind == "corrupt":
                buf = bytearray(data)
                # STRUCTURAL corruption: flip a byte in the frame's
                # leading structure (lane counts / status / lens planes)
                # — the region the existing frame validation rejects
                # loudly. Never the 4-byte outer length prefix (a
                # corrupted LENGTH leaves the daemon blocked reading
                # bytes that never come — its reads are unbudgeted by
                # design, trusted local IPC), and not arbitrary payload
                # bytes either: on a checksummed local socket a flipped
                # sig/msg byte models memory corruption, not transport
                # failure, and is undetectable BY DESIGN (docs
                # "Failure model") — injecting it would assert a
                # contract the protocol does not make
                if len(buf) > 5:
                    off = self._plan.corrupt_offset(4, min(len(buf), 12))
                    buf[off] ^= 0xFF
                data = bytes(buf)
        return self._sock.sendall(data)

    def recv(self, n: int) -> bytes:
        if self._len_rem > 0:
            # length-prefix bytes: pass through untouched — a flipped
            # length desynchronizes the framing into a silent
            # both-sides wedge, modeling nothing the protocol can
            # detect (docs "Failure model")
            data = self._sock.recv(min(n, self._len_rem))
            self._len_rem -= len(data)
            self._len_acc += data
            if self._len_rem == 0:
                (self._frame_rem,) = struct.unpack(">I", self._len_acc)
                self._len_acc = b""
                self._frame_new = True
                if self._frame_rem == 0:  # empty frame: next is a new one
                    self._len_rem = 4
            return data
        f = None
        if self._frame_new:  # first payload read of this frame
            self._frame_new = False
            f = self._plan.pick("s2c", supported=("stall", "drop", "corrupt"))
        if f is not None:
            if f.kind == "stall":
                time.sleep(f.stall_s)
            elif f.kind == "drop":
                _kill_sock(self._sock)
                raise ConnectionError("fault: connection dropped mid-read")
        data = bytearray(self._sock.recv(min(n, self._frame_rem)))
        self._frame_rem -= len(data)
        if self._frame_rem == 0:
            self._len_rem = 4
        if f is not None and f.kind == "corrupt" and data:
            # structural head of the frame (status/index/counts)
            data[self._plan.corrupt_offset(0, min(len(data), 9))] ^= 0xFF
        return bytes(data)

    # -- plain delegation ---------------------------------------------------

    def settimeout(self, t) -> None:
        self._sock.settimeout(t)

    def shutdown(self, how) -> None:
        self._sock.shutdown(how)

    def close(self) -> None:
        self._sock.close()

    def fileno(self) -> int:
        return self._sock.fileno()


def install_client_faults(plan: FaultPlan) -> FaultPlan:
    """Route every NEW DevdClient connection in this process through the
    plan (devd.set_socket_wrapper — the sanctioned injection point; no
    client internals are monkeypatched). Connect-stream faults fire at
    wrap time: `refuse` closes the fresh socket and raises
    ConnectionRefusedError exactly as a dead daemon would. Pair with
    `uninstall_client_faults()` in test teardown."""
    from tendermint_tpu import devd

    def wrap(sock: socket.socket):
        f = plan.pick("connect", supported=("refuse", "stall"))
        if f is not None and f.kind == "refuse":
            sock.close()
            raise ConnectionRefusedError("fault: connect refused")
        if f is not None and f.kind == "stall":
            time.sleep(f.stall_s)
        return FaultSocket(sock, plan)

    devd.set_socket_wrapper(wrap)
    return register(plan)


def uninstall_client_faults(plan: FaultPlan | None = None) -> None:
    from tendermint_tpu import devd

    devd.set_socket_wrapper(None)
    if plan is not None:
        unregister(plan)


# -- out-of-process deployment: wire shim in front of a real daemon -----------


# the proxy reads frames with the REAL client/daemon read loop — if its
# semantics ever change (error taxonomy, interrupt handling), the
# byte-for-byte relay guarantee must change with them, not drift
from tendermint_tpu.devd import _recv_exact  # noqa: E402


def _kill_sock(s: socket.socket) -> None:
    """shutdown THEN close. close() alone from another thread does NOT
    wake a recv blocked on the same fd (the in-flight syscall pins the
    file description, so no FIN ever goes out and BOTH sides hang —
    exactly the wedge the first chaos soak caught in the relay
    teardown); shutdown() tears the connection down immediately."""
    try:
        s.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        s.close()
    except Exception:  # noqa: BLE001 — teardown best effort
        pass


def _is_stream_header(payload: bytes) -> bool:
    """Is this c2s frame a verify_stream/hash_stream header? (Binary
    chunk frames virtually never unpickle; a failed loads is a clean
    'no'.)"""
    try:
        obj = pickle.loads(payload)
    except Exception:  # noqa: BLE001 — binary chunk frame, not a header
        return False
    return isinstance(obj, dict) and str(obj.get("op", "")).endswith("_stream")


class FaultProxy:
    """Frame-aware UDS shim between DevdClients and a real daemon: both
    planes' wire framing crosses byte-for-byte (length prefix + payload
    relayed as read), and the plan injects at frame granularity — so a
    `corrupt` lands inside a real chunk/digest frame, a `truncate` cuts
    a real frame mid-payload, and `skew` answers a *_stream header with
    the pickle error a pre-streaming daemon would send (the client's
    version-skew latch path). `blackout()` emulates daemon death without
    touching the daemon: live connections drop and new connects refuse
    for the window. Runs as threads in-process, or standalone via
    `python -m tendermint_tpu.ops.faults`."""

    def __init__(self, listen_path: str, upstream_path: str,
                 plan: FaultPlan | None = None):
        self.listen_path = listen_path
        self.upstream_path = upstream_path
        self.plan = plan if plan is not None else FaultPlan()
        self._srv: socket.socket | None = None
        self._stop = threading.Event()
        self._conns: list[socket.socket] = []
        self._mtx = threading.Lock()
        self._blackout_until = 0.0
        self._accept_thread: threading.Thread | None = None

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "FaultProxy":
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        if os.path.exists(self.listen_path):
            os.unlink(self.listen_path)
        srv.bind(self.listen_path)
        os.chmod(self.listen_path, 0o600)
        srv.listen(64)
        srv.settimeout(0.5)
        self._srv = srv
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="fault-proxy-accept"
        )
        self._accept_thread.start()
        register(self.plan)
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)
        if self._srv is not None:
            self._srv.close()
        try:
            os.unlink(self.listen_path)
        except OSError:
            pass
        self._drop_all()
        unregister(self.plan)

    def blackout(self, seconds: float) -> None:
        """Daemon-death emulation for `kill` schedules that must not
        actually SIGKILL (e.g. a shared daemon): refuse new connects and
        drop live ones for the window."""
        with self._mtx:
            self._blackout_until = time.monotonic() + seconds
        self.plan.note("kill")
        self._drop_all()

    def _drop_all(self) -> None:
        with self._mtx:
            conns, self._conns = self._conns, []
        for c in conns:
            _kill_sock(c)

    # -- relay --------------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            now = time.monotonic()
            with self._mtx:
                dark = now < self._blackout_until
            f = None if dark else self.plan.pick(
                "connect", supported=("refuse", "stall")
            )
            if dark or (f is not None and f.kind == "refuse"):
                conn.close()
                continue
            if f is not None and f.kind == "stall":
                time.sleep(f.stall_s)
            try:
                up = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                up.settimeout(5.0)
                up.connect(self.upstream_path)
                up.settimeout(None)
            except OSError:
                # upstream daemon down: the client sees exactly what a
                # dead daemon produces — an immediately closed conn
                conn.close()
                continue
            with self._mtx:
                self._conns += [conn, up]
            threading.Thread(
                target=self._relay, args=(conn, up, "c2s"),
                daemon=True, name="fault-proxy-c2s",
            ).start()
            threading.Thread(
                target=self._relay, args=(up, conn, "s2c"),
                daemon=True, name="fault-proxy-s2c",
            ).start()

    def _relay(self, src: socket.socket, dst: socket.socket,
               direction: str) -> None:
        try:
            while not self._stop.is_set():
                hdr = _recv_exact(src, 4)
                (n,) = struct.unpack(">I", hdr)
                payload = _recv_exact(src, n)
                supported = ["stall", "drop", "truncate", "corrupt"]
                # skew only injects on a frame that actually IS a stream
                # header — advertise it as supported only then, so a due
                # skew rule is never consumed (or counted) by a frame it
                # cannot apply to
                if direction == "c2s" and self.plan.wants("skew", "c2s") \
                        and _is_stream_header(payload):
                    supported.append("skew")
                f = self.plan.pick(direction, supported=supported)
                if f is not None:
                    if f.kind == "stall":
                        time.sleep(f.stall_s)
                    elif f.kind == "drop":
                        return
                    elif f.kind == "truncate":
                        dst.sendall(hdr + payload[: max(1, n // 2)])
                        return
                    elif f.kind == "corrupt" and n > 1:
                        # structural region only (status/index/counts/
                        # lens planes) — see FaultSocket.sendall: flips
                        # the validation layer detects, not silent
                        # payload rot the trusted-IPC contract excludes
                        buf = bytearray(payload)
                        buf[self.plan.corrupt_offset(0, min(n, 9))] ^= 0xFF
                        payload = bytes(buf)
                    elif f.kind == "skew" and direction == "c2s" \
                            and _is_stream_header(payload):
                        # answer like a pre-streaming daemon and swallow
                        # the header: the client must latch single-shot,
                        # not hang
                        rep = pickle.dumps(
                            {"ok": False, "error": "unknown op (skewed)"}
                        )
                        src.sendall(struct.pack(">I", len(rep)) + rep)
                        continue
                dst.sendall(hdr + payload)
        except (ConnectionError, OSError, struct.error):
            pass
        finally:
            for s in (src, dst):
                _kill_sock(s)


# -- daemon churn: the kill/restart arm of a chaos schedule -------------------


class DaemonSupervisor:
    """Spawn, SIGKILL, and restart a devd daemon on a schedule. Chip-free
    by construction: refuses any environment that is not ACCEPT_CPU — a
    real device owner is stopped through its `shutdown` op, never by a
    harness's SIGKILL. Kills note `faults_kill` on the
    plan, so the chaos tests can assert the schedule actually fired."""

    def __init__(self, sock_path: str, extra_env: dict | None = None,
                 plan: FaultPlan | None = None):
        env = dict(extra_env or {})
        env.setdefault("TENDERMINT_DEVD_ACCEPT_CPU", "1")
        if env.get("TENDERMINT_DEVD_ACCEPT_CPU") != "1":
            raise ValueError(
                "DaemonSupervisor only supervises ACCEPT_CPU daemons: "
                "a real device owner is stopped through its shutdown op "
                "(tendermint_tpu/devd.py)"
            )
        self.sock_path = sock_path
        self.extra_env = env
        self.plan = plan
        self.proc: subprocess.Popen | None = None
        # daemon stderr goes to a FILE, not a pipe: nothing drains a
        # pipe while the daemon serves, so a chatty daemon (INFO
        # logging + jax warnings) would fill the 64 KB pipe buffer and
        # block inside its own logging call mid-soak — a fake liveness
        # failure. The file doubles as the death report.
        self.log_path = os.path.join(
            tempfile.gettempdir(),
            f"devd-supervised-{os.getpid()}-{id(self):x}.log",
        )
        self._churn_stop = threading.Event()
        self._churn_thread: threading.Thread | None = None
        self.kills = 0
        self.restarts = 0

    def start(self, wait_held_s: float = 30.0) -> None:
        if self.plan is not None:
            # kills noted on the plan must be scrape-visible (round 11):
            # register it so global_counters()/the telemetry producer
            # aggregate it like the injection harnesses' plans
            register(self.plan)
        repo = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ))
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "TENDERMINT_DEVD_SOCK": self.sock_path,
            "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
            **self.extra_env,
        }
        # a sharded-plane harness exports the fleet's endpoint list; the
        # daemon itself must bind exactly ITS socket, never consult the
        # fleet topology
        env.pop("TENDERMINT_DEVD_SOCKS", None)
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tendermint_tpu.devd"],
                env=env, cwd=repo,
                stdout=subprocess.DEVNULL, stderr=log,
            )
        if wait_held_s > 0:
            self.wait_held(wait_held_s)

    def _log_tail(self, nbytes: int = 2000) -> bytes:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - nbytes))
                return f.read()
        except OSError:
            return b""

    def wait_held(self, deadline_s: float) -> dict:
        from tendermint_tpu import devd

        client = devd.DevdClient(self.sock_path)
        deadline = time.time() + deadline_s
        try:
            while time.time() < deadline:
                if self.proc is not None and self.proc.poll() is not None:
                    raise RuntimeError(
                        f"supervised daemon died: {self._log_tail()!r}"
                    )
                try:
                    rep = client.ping(timeout=2.0)
                    if rep.get("held"):
                        return rep
                except Exception:  # noqa: BLE001 — not serving yet
                    pass
                time.sleep(0.1)
            raise TimeoutError(
                f"daemon on {self.sock_path} never reached serving state"
            )
        finally:
            client.close()

    def kill(self) -> None:
        """SIGKILL — the fault being modeled is an unclean death, so no
        graceful shutdown op (and devd ignores SIGTERM by design)."""
        if self.proc is None:
            return
        try:
            self.proc.kill()
            self.proc.wait(timeout=15)
        except Exception:  # noqa: BLE001 — reaped elsewhere / already gone
            pass
        self.proc = None
        self.kills += 1
        _note_supervisor("kills")
        if self.plan is not None:
            self.plan.note("kill")

    def restart(self, wait_held_s: float = 30.0) -> None:
        self.kill()
        # an unclean kill leaves the bound socket file behind; devd's own
        # startup probe handles the stale socket, so just restart
        self.start(wait_held_s=wait_held_s)
        self.restarts += 1
        _note_supervisor("restarts")

    def churn(self, down_s: float = 0.5, up_s: float = 2.0,
              cycles: int = 0) -> None:
        """Background kill/restart loop: daemon down for down_s, up for
        up_s, `cycles` times (0 = until stop_churn). Always exits with
        the daemon RUNNING so recovery is observable."""

        def run() -> None:
            n = 0
            while not self._churn_stop.is_set():
                if cycles and n >= cycles:
                    break
                self.kill()
                if self._churn_stop.wait(down_s):
                    break
                try:
                    self.start(wait_held_s=30.0)
                except Exception:  # noqa: BLE001 — restart raced stop()
                    logger.exception("chaos restart failed")
                    break
                self.restarts += 1
                _note_supervisor("restarts")
                n += 1
                if self._churn_stop.wait(up_s):
                    break
            if self.proc is None and not self._churn_stop.is_set():
                try:
                    self.start(wait_held_s=30.0)
                except Exception:  # noqa: BLE001 — leave down; stop() reaps
                    logger.exception("final chaos restart failed")

        self._churn_stop.clear()
        self._churn_thread = threading.Thread(
            target=run, daemon=True, name="chaos-churn"
        )
        self._churn_thread.start()

    def stop_churn(self, ensure_up: bool = True) -> None:
        self._churn_stop.set()
        if self._churn_thread is not None:
            self._churn_thread.join(timeout=60.0)
            self._churn_thread = None
        if ensure_up and self.proc is None:
            self.start(wait_held_s=30.0)

    def stop(self) -> None:
        self._churn_stop.set()
        if self._churn_thread is not None:
            self._churn_thread.join(timeout=60.0)
        if self.proc is not None:
            try:
                self.proc.kill()
                self.proc.wait(timeout=15)
            except Exception:  # noqa: BLE001 — already gone
                pass
            self.proc = None
        if self.plan is not None:
            unregister(self.plan)


class DaemonFleet:
    """N supervised sim daemons on distinct sockets — the sharded device
    plane's chaos/bench substrate (round 21). Same ACCEPT_CPU-only rule
    as DaemonSupervisor (which it composes); `sock_paths` joins directly
    into TENDERMINT_DEVD_SOCKS."""

    def __init__(self, n: int, sock_dir: str | None = None,
                 extra_env: dict | None = None):
        base = sock_dir or tempfile.gettempdir()
        self.supervisors = [
            DaemonSupervisor(
                # short: a unix socket's path may have 107 bytes, and a
                # test's tmp_path under xdist leaves some 35 of them
                os.path.join(
                    base,
                    f"fleet-{os.getpid()}-{id(self) >> 4 & 0xffffff:x}-{i}.sock",
                ),
                extra_env=dict(extra_env or {}),
            )
            for i in range(n)
        ]

    @property
    def sock_paths(self) -> list[str]:
        return [s.sock_path for s in self.supervisors]

    @property
    def socks_env(self) -> str:
        """The TENDERMINT_DEVD_SOCKS value for this fleet."""
        return ",".join(self.sock_paths)

    def start(self, wait_held_s: float = 30.0) -> "DaemonFleet":
        started = []
        try:
            for s in self.supervisors:
                s.start(wait_held_s=wait_held_s)
                started.append(s)
        except BaseException:
            for s in started:
                s.stop()
            raise
        return self

    def kill(self, i: int) -> None:
        self.supervisors[i].kill()

    def restart(self, i: int, wait_held_s: float = 30.0) -> None:
        self.supervisors[i].restart(wait_held_s=wait_held_s)

    def stop(self) -> None:
        for s in self.supervisors:
            s.stop()


# -- standalone shim process --------------------------------------------------


def main(argv=None) -> int:
    """Run a FaultProxy as its own process (multi-process harnesses —
    localnet nodes point TENDERMINT_DEVD_SOCK at --listen). The schedule
    is built from the repeat-rate flags; counters print as ONE json line
    on SIGTERM/SIGINT."""
    ap = argparse.ArgumentParser(description=FaultProxy.__doc__)
    ap.add_argument("--listen", required=True, help="UDS path to serve")
    ap.add_argument("--upstream", required=True, help="real daemon socket")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--corrupt-every", type=int, default=0,
                    help="corrupt every Nth daemon->client frame")
    ap.add_argument("--truncate-every", type=int, default=0,
                    help="truncate every Nth client->daemon frame")
    ap.add_argument("--stall-every", type=int, default=0,
                    help="stall every Nth daemon->client frame")
    ap.add_argument("--stall-s", type=float, default=0.5)
    args = ap.parse_args(argv)

    plan = FaultPlan(seed=args.seed)
    big = 1 << 30  # rate rules: fire forever at the given cadence
    if args.corrupt_every:
        plan.add("corrupt", "s2c", first=args.corrupt_every,
                 every=args.corrupt_every, limit=big)
    if args.truncate_every:
        plan.add("truncate", "c2s", first=args.truncate_every,
                 every=args.truncate_every, limit=big)
    if args.stall_every:
        plan.add("stall", "s2c", first=args.stall_every,
                 every=args.stall_every, limit=big, stall_s=args.stall_s)

    proxy = FaultProxy(args.listen, args.upstream, plan).start()
    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    logging.basicConfig(level=logging.INFO)
    logger.info("fault proxy %s -> %s", args.listen, args.upstream)
    done.wait()
    proxy.stop()
    print(json.dumps(plan.stats()))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
