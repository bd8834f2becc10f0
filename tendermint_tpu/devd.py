"""Device-access daemon: ONE long-lived process owns the accelerator.

Why this exists: libtpu gives a chip to ONE process and keeps it (and
its lock file) until that process exits — a second process that loads
the library while the first lives fails at start-up. A host runs many
processes that want signatures verified (validator nodes, benches,
tests), so exactly one of them may own the chip:

- devd is the ONLY process that initialises the device. It claims the
  chip in-process (jax.devices() either answers or raises), warms the
  verify kernels at production shapes, checks that every warm lane was
  answered by the device, and then serves verify batches over a
  root-only unix socket.
- Everything else (benches, tests, live nodes) talks to devd through
  DevdClient / ops/devd_backend.py and stays on JAX_PLATFORMS=cpu — so
  starting, killing or restarting a node never contends for the chip.
- One daemon per chip: on a multi-chip host the LAUNCHER starts N
  daemons, each with the libtpu environment that shows it one chip
  (TPU_VISIBLE_CHIPS & co — chip_smoke.py --chips 4 is the model), and
  lists their sockets in TENDERMINT_DEVD_SOCKS (ops/devd_shard).
- devd itself ignores SIGTERM (set TENDERMINT_DEVD_EXIT_ON_TERM=1 to
  allow graceful exit, e.g. in tests and chip_smoke.py) and is started
  detached (setsid) so an interactive session ending doesn't reap it.
- A claim that fails is FINAL: the device could not be initialised, or
  a kernel was refused, or a warm lane was answered by the host. The
  daemon reports status `failed` with the error text in `ping`, logs
  the exception (libtpu's message included) and exits non-zero. It
  never retries in a loop and never serves the host verifier under the
  device's name.

The reference runs its signature checks inline per process
(types/validator_set.go:220-264); a per-host device daemon is the
TPU-native replacement: one chip, one owner, many client processes.

Wire protocol (trusted local IPC, socket mode 0600, root-only box):
4-byte big-endian length + pickled dict. Requests: {"op": "ping" |
"verify" | "verify_stream" | "agg" | "hash" | "hash_stream" | "stats" |
"status" | "spans" | "profile" | "shutdown", ...}. Replies: {"ok": bool, ...}.

Every verifier call leaves one record of five phases in a ring, taken
from inside (tendermint_tpu/devd_spans.py): the `spans` op serves it,
`serve()` writes it out when it returns, and the `profile` op starts and
stops a `jax.profiler` trace that holds the same phases as annotations.

Single-shot `verify` requests that wait at the same instant ride ONE
program (`_VerifyMerger`, PR 27): a committee's validators ask for the
same few verdicts within milliseconds of each other, and a program costs
the same host crossing whether it carries one lane or 256. Each caller
gets the verdicts of its own lanes; each request's record says which
program it rode and with how many others.

Streaming transport (round 6 — docs/streaming-devd.md): the single-shot
"verify" op serializes the WHOLE batch into one pickle frame and blocks
for one monolithic round trip, which caps the serving path well below
what the kernel sustains. The
"verify_stream" op replaces that with a pipelined data plane on the same
connection:

  client -> {"op": "verify_stream", "chunks": K, "total": N}   (pickle)
  client -> K binary chunk frames (no pickle; see _pack_chunk)
  daemon -> K binary result frames, one per chunk, IN ORDER, each sent
            the moment that chunk's verdicts land on host

The daemon double-buffers: chunk N+1 is read off the socket and decoded
(np.frombuffer over contiguous pubkey/msg_len/msg/sig planes) while
chunk N is still in the device kernel (verify_batch_async), up to
TENDERMINT_DEVD_STREAM_DEPTH chunks in flight. A malformed chunk frame
answers with an error result frame (status 1) and closes the stream —
never a hang. Accept/reject semantics are lane-for-lane identical to
the single-shot op (same Verifier underneath).

Hash plane (round 7 — same doc): the "hash" / "hash_stream" ops extend
the chunked data plane to the Merkle workload, which loses badly
through single monolithic round trips. A hash chunk frame carries contiguous
leaf planes (lengths + packed bytes, np.frombuffer decode), each chunk
dispatches to the batched RIPEMD-160 kernel as it decodes, and 20-byte
digests stream back per chunk in order under the same in-flight bound
and malformed-frame semantics. With "tree": true the daemon runs the
vectorized tree kernel over the accumulated leaf digests after the last
chunk and appends ONE tree frame carrying every internal node
(postorder — merkle.simple.FlatTree slot order), so part-set proofs
cost the host zero hashing. Digests are byte-identical to
crypto.hashing.ripemd160 / merkle.simple (parity-tested).
"""

from __future__ import annotations

import itertools
import logging
import os
import pickle
import queue as queuelib
import signal
import socket
import struct
import sys
import threading
import time

# env-tunable deadline budgets parse via the shared defensive knob helper:
# a typo'd value must not kill the verify hot path (libs.envknob is
# stdlib-only, so the daemon's light import footprint is preserved)
from tendermint_tpu import devd_spans
from tendermint_tpu.libs.envknob import env_number as _env_timeout

logger = logging.getLogger("devd")

DEFAULT_SOCK = "/tmp/tendermint-devd.sock"

# streamed-chunk lane bound: a frame claiming more lanes than this is
# malformed by definition (1M lanes ~ 100MB+ of signatures)
_MAX_CHUNK_LANES = 1 << 20
# default chunk width when neither the daemon's claim-time tuning nor
# TENDERMINT_DEVD_CHUNK pinned one
DEFAULT_STREAM_CHUNK = 2048
# writer-thread reap budget (DevdClient._reap_writer); module-level so
# the chaos tests can shrink it without waiting out the production value
WRITER_REAP_S = 5.0


def sock_path() -> str:
    """The PRIMARY daemon socket. TENDERMINT_DEVD_SOCK pins it; without
    one, the first entry of TENDERMINT_DEVD_SOCKS (the round-21 sharded
    device plane's endpoint list, ops/devd_shard) is the primary — so a
    one-entry SOCKS deployment behaves byte-for-byte like a SOCK one."""
    explicit = os.environ.get("TENDERMINT_DEVD_SOCK")
    if explicit:
        return explicit
    for p in os.environ.get("TENDERMINT_DEVD_SOCKS", "").split(","):
        p = p.strip()
        if p:
            return p
    return DEFAULT_SOCK


# -- framing ------------------------------------------------------------------


def _send_frame(conn: socket.socket, obj) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    conn.sendall(struct.pack(">I", len(payload)) + payload)


def _recv_exact(conn: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("devd peer closed")
        buf += chunk
    return buf


def _recv_frame_len(conn: socket.socket) -> int:
    """A frame's length header. On a pooled connection this is where a
    handler waits idle between requests: a record's t_recv0 is taken
    AFTER it returns."""
    (n,) = struct.unpack(">I", _recv_exact(conn, 4))
    if n > (1 << 30):
        raise ValueError(f"devd frame too large: {n}")
    return n


def _recv_raw_frame(conn: socket.socket) -> bytes:
    """Length-prefixed frame WITHOUT unpickling — stream chunk/result
    frames are binary, not pickle."""
    return _recv_exact(conn, _recv_frame_len(conn))


def _recv_frame(conn: socket.socket):
    return pickle.loads(_recv_raw_frame(conn))


# -- stream chunk codec -------------------------------------------------------
#
# One chunk frame carries n verify lanes as four contiguous planes —
#   u32 n | pubkeys 32*n | sigs 64*n | msg_lens u32*n | msgs concat
# — so the daemon decodes with np.frombuffer over the received buffer
# (no per-item pickling on either side). Result frame payloads:
#   status u8 (0=ok) | index u32 | n u32 | verdicts u8*n
#   status u8 (1=err) | index u32 | utf-8 error message
# An error frame terminates the stream; the daemon closes the connection
# after sending it (framing past a malformed chunk is untrustworthy).

STREAM_OK = 0
STREAM_ERR = 1
# hash_stream only: the post-chunk frame carrying the tree's internal
# nodes (postorder) when the request asked for "tree": true
STREAM_TREE = 2

# hash modes: "part" = raw ripemd160 per item (Part.Hash), "leaf" =
# ripemd160 of the length-prefixed item (merkle.simple.leaf_hash)
HASH_MODES = ("part", "leaf")


def _pack_chunk(items) -> bytes:
    """items: [(pubkey32, msg, sig64)] -> one chunk frame payload.
    List-comprehension planes + one join each: the whole pack is C-loop
    work (measured ~8x a per-item append loop; pickling the same items
    costs more AND forces the daemon through per-item pickle decode)."""
    import numpy as np

    n = len(items)
    pks = [it[0] for it in items]
    msgs = [it[1] for it in items]
    sigs = [it[2] for it in items]
    if any(len(pk) != 32 for pk in pks) or any(len(s) != 64 for s in sigs):
        bad = next(
            i for i, it in enumerate(items)
            if len(it[0]) != 32 or len(it[2]) != 64
        )
        raise ValueError(
            f"stream lane {bad}: pubkey/sig must be 32/64 bytes "
            f"(got {len(items[bad][0])}/{len(items[bad][2])}); "
            "route non-ed25519 via CPU"
        )
    lens = np.fromiter(map(len, msgs), dtype="<u4", count=n)
    return b"".join((
        struct.pack("<I", n),
        b"".join(pks),
        b"".join(sigs),
        lens.tobytes(),
        b"".join(msgs),
    ))


def _unpack_chunk(payload: bytes) -> list:
    """Inverse of _pack_chunk; raises ValueError on any malformed frame.
    Plane-sliced decode: lens via ONE np.frombuffer, fixed-width planes
    via C-level bytes slicing — no per-item pickle, no memoryview churn
    (bytes(memoryview[...]) measured 6x slower than plane slicing)."""
    import numpy as np

    if len(payload) < 4:
        raise ValueError("chunk frame shorter than its lane count")
    (n,) = struct.unpack_from("<I", payload, 0)
    if n > _MAX_CHUNK_LANES:
        raise ValueError(f"chunk claims {n} lanes (max {_MAX_CHUNK_LANES})")
    off_sig = 4 + n * 32
    off_len = off_sig + n * 64
    fixed = off_len + n * 4
    if fixed > len(payload):
        raise ValueError(
            f"chunk truncated: {len(payload)} bytes < {fixed} fixed planes"
        )
    lens_arr = np.frombuffer(payload, dtype="<u4", count=n, offset=off_len)
    if fixed + int(lens_arr.sum()) != len(payload):
        raise ValueError(
            f"chunk size mismatch: {len(payload)} != "
            f"{fixed + int(lens_arr.sum())}"
        )
    pk_plane = payload[4:off_sig]
    sig_plane = payload[off_sig:off_len]
    pks = [pk_plane[i: i + 32] for i in range(0, n * 32, 32)]
    sigs = [sig_plane[i: i + 64] for i in range(0, n * 64, 64)]
    msgs, mo = [], fixed
    for ln in lens_arr.tolist():
        msgs.append(payload[mo: mo + ln])
        mo += ln
    return list(zip(pks, msgs, sigs))


def _send_result_frame(conn: socket.socket, index: int, oks) -> None:
    import numpy as np

    payload = struct.pack("<BII", STREAM_OK, index, len(oks)) + (
        np.asarray(oks, dtype=np.uint8).tobytes()
    )
    conn.sendall(struct.pack(">I", len(payload)) + payload)


# -- hash chunk codec ---------------------------------------------------------
#
# One hash chunk frame carries n leaf payloads as two contiguous planes —
#   u32 n | lens u32*n | payload bytes concatenated
# — decoded daemon-side with ONE np.frombuffer for the lengths plus
# C-level bytes slicing for the payloads (no per-item pickling). Digest
# result frames:
#   status u8 (0=ok) | index u32 | n u32 | digests 20*n
#   status u8 (1=err) | index u32 | utf-8 error message
#   status u8 (2=tree) | count u32 | internal nodes 20*count  (postorder;
#            sent once, after the last chunk's digests, iff "tree": true)
# Error semantics match the verify stream: an error frame terminates the
# stream and the daemon closes the connection.


def _pack_hash_chunk(items) -> bytes:
    """items: [bytes] -> one hash chunk frame payload (lengths plane +
    packed bytes; list-join C-loop work, mirroring _pack_chunk)."""
    import numpy as np

    n = len(items)
    lens = np.fromiter(map(len, items), dtype="<u4", count=n)
    return b"".join((struct.pack("<I", n), lens.tobytes(), b"".join(items)))


def _unpack_hash_chunk(payload: bytes) -> list:
    """Inverse of _pack_hash_chunk; raises ValueError on any malformed
    frame (same validation discipline as _unpack_chunk)."""
    import numpy as np

    if len(payload) < 4:
        raise ValueError("hash chunk frame shorter than its item count")
    (n,) = struct.unpack_from("<I", payload, 0)
    if n > _MAX_CHUNK_LANES:
        raise ValueError(f"hash chunk claims {n} items (max {_MAX_CHUNK_LANES})")
    fixed = 4 + n * 4
    if fixed > len(payload):
        raise ValueError(
            f"hash chunk truncated: {len(payload)} bytes < {fixed} length plane"
        )
    lens_arr = np.frombuffer(payload, dtype="<u4", count=n, offset=4)
    if fixed + int(lens_arr.sum()) != len(payload):
        raise ValueError(
            f"hash chunk size mismatch: {len(payload)} != "
            f"{fixed + int(lens_arr.sum())}"
        )
    items, off = [], fixed
    for ln in lens_arr.tolist():
        items.append(payload[off: off + ln])
        off += ln
    return items


def _send_digest_frame(conn: socket.socket, index: int, digests) -> None:
    payload = struct.pack("<BII", STREAM_OK, index, len(digests)) + b"".join(
        digests
    )
    conn.sendall(struct.pack(">I", len(payload)) + payload)


def _send_tree_frame(conn: socket.socket, nodes) -> None:
    payload = struct.pack("<BI", STREAM_TREE, len(nodes)) + b"".join(nodes)
    conn.sendall(struct.pack(">I", len(payload)) + payload)


def _send_error_frame(conn: socket.socket, index: int, msg: str) -> None:
    payload = struct.pack("<BI", STREAM_ERR, index) + msg.encode()
    conn.sendall(struct.pack(">I", len(payload)) + payload)


# -- server -------------------------------------------------------------------


class _DaemonState:
    def __init__(self):
        self.started = time.time()
        self.platform: str | None = None
        self.verifier = None  # ops.gateway.Verifier once the device is held
        self.hasher = None    # hash backend once the device is held
        self.warmed: list[int] = []
        self.status = "starting"
        # a failed claim: the error text (status "failed"); the daemon
        # answers pings with it until one client has read it, then exits
        self.error: str | None = None
        self.failed_seen = threading.Event()
        # what jax.devices() reported to the owner, and what the claim
        # measured (cache dir, per-kernel warm seconds, bake-off rates)
        self.device_kind: str | None = None
        self.device_count: int | None = None
        self.device_ids: list[int] = []
        self.claim: dict = {}
        self.lock = threading.Lock()
        self.stop = threading.Event()
        # one record per verifier call, taken from inside (devd_spans):
        # the `spans` op and the dump at stop read the ring, the
        # `profile` op drives the profiler
        self.spans = devd_spans.SpanRing()
        self.profile = devd_spans.Profile(self.spans)
        self.conn_ids = itertools.count(1)
        # client connections open now (a gauge: ping, status and the
        # records' header carry it) and the most there ever were
        self.conns_open = 0
        self.conns_open_max = 0
        # single-shot `verify` requests that wait at the same instant
        # ride one program (_VerifyMerger)
        self.merger = _VerifyMerger(self)
        # claim-time-tuned streamed chunk width, advertised in ping/status
        # so clients frame at the width the held device actually likes
        self.stream_chunk = int(
            os.environ.get("TENDERMINT_DEVD_CHUNK") or "0"
        ) or DEFAULT_STREAM_CHUNK
        # serving-path observability (ISSUE 1): how the streamed data
        # plane is doing in production, not just in benches
        self.stream = {
            "streams": 0,            # verify_stream requests served
            "chunks": 0,             # chunk frames verified
            "lanes": 0,              # signatures through the stream path
            "bytes_framed": 0,       # chunk-frame payload bytes received
            "inflight": 0,           # chunks currently dispatched, unresolved
            "inflight_max": 0,       # high-water mark (proves overlap)
            "errors": 0,             # malformed/aborted streams
            "chunk_device_ms_last": 0.0,   # dispatch->verdict, last chunk
            "chunk_device_ms_avg": 0.0,    # EWMA (alpha .2) of the same
        }
        # hash-plane observability (ISSUE 2): same gauge shape as the
        # verify stream, "lanes" = leaves hashed; plus the tree-frame and
        # single-shot hash-op counters
        self.hash_stream = {
            "streams": 0,
            "chunks": 0,
            "lanes": 0,
            "bytes_framed": 0,
            "inflight": 0,
            "inflight_max": 0,
            "errors": 0,
            "trees": 0,              # tree frames served (proof-free part sets)
            "single_batches": 0,     # single-shot "hash" op requests
            "single_lanes": 0,
            "chunk_device_ms_last": 0.0,
            "chunk_device_ms_avg": 0.0,
        }

    def stream_stats(self) -> dict:
        with self.lock:
            return dict(self.stream)

    def hash_stream_stats(self) -> dict:
        with self.lock:
            return dict(self.hash_stream)


# The merge of waiting single-shot `verify` requests (_VerifyMerger; why:
# the module's docstring). What chooses is what is waiting: a request that
# meets nobody runs alone, as it always did. Constants, not knobs: a
# merged program never passes MERGE_MAX_LANES (a single request wider
# than that runs alone), and MERGE_TURNS programs are in flight at once,
# so that the next one marshals while the device runs the last. And a
# merge never makes a SHAPE the daemon has not run yet: requests join
# only while the padded width of the whole (_width) is one a program has
# already had here, or the width the first request has alone. A new width
# is a new program to trace and compile, seconds inside somebody's wait;
# whoever warms the daemon decides which widths exist.
MERGE_MAX_LANES = 256
MERGE_TURNS = 2


def _width(lanes: int) -> int:
    """The bucket the verify kernels pad a batch to (a power of two, 8 at
    the least: ops/ed25519_f32._next_pow2)."""
    return max(8, 1 << max(0, lanes - 1).bit_length())


class _Waiting:
    __slots__ = ("items", "rec", "conn", "done", "oks", "err")

    def __init__(self, items, rec, conn: int):
        self.items = items
        self.rec = rec
        self.conn = conn
        self.done = threading.Event()
        self.oks = None
        self.err: BaseException | None = None


class _VerifyMerger:
    """The daemon's queue of single-shot verify requests and the
    MERGE_TURNS threads that serve it. A turn takes the oldest waiting
    request and every other one that fits beside it, verifies their lanes
    as one batch on the daemon's verifier, and hands each caller the
    verdicts of its own lanes: a forged lane of one caller is False in
    that caller's reply and nowhere else. A batch that raises is run
    again one request at a time, so that only the request at fault gets
    the error. Each request's record notes the program it rode
    (devd_spans.CallRecord.ride)."""

    def __init__(self, st: "_DaemonState"):
        self._st = st
        self._cond = threading.Condition()
        self._queue: list[_Waiting] = []
        self._threads: list[threading.Thread] = []
        self._widths_run: set[int] = set()   # padded widths programs had
        # flat counters (status op): programs run, the requests and lanes
        # they carried, programs that carried more than one request, and
        # the most requests one program ever carried
        self.stats = {"programs": 0, "requests": 0, "lanes": 0,
                      "merged_programs": 0, "requests_max": 0}

    def verify(self, items, rec, conn: int):
        """Called on a connection's thread: blocks for this request's
        verdicts; raises what the verifier raised for it."""
        w = _Waiting(items, rec, conn)
        with self._cond:
            if not self._threads:
                for k in range(MERGE_TURNS):
                    t = threading.Thread(target=self._turns, daemon=True,
                                         name=f"devd-verify-{k}")
                    t.start()
                    self._threads.append(t)
            self._queue.append(w)
            self._cond.notify()
        w.done.wait()
        if w.err is not None:
            raise w.err
        return w.oks

    def _take(self) -> list[_Waiting]:
        """The oldest request and, in arrival order, every other that
        fits beside it under MERGE_MAX_LANES without making a width no
        program has had yet."""
        q = self._queue
        group = [q.pop(0)]
        lanes = len(group[0].items)
        i = 0
        while i < len(q) and lanes < MERGE_MAX_LANES:
            total = lanes + len(q[i].items)
            if total <= MERGE_MAX_LANES and (
                _width(total) == _width(lanes)
                or _width(total) in self._widths_run
            ):
                lanes = total
                group.append(q.pop(i))
            else:
                i += 1
        return group

    def _turns(self) -> None:
        while True:
            with self._cond:
                while not self._queue:
                    self._cond.wait()
                group = self._take()
                if self._queue:
                    self._cond.notify()
            try:
                self._run(group)
            except BaseException as exc:  # noqa: BLE001 — a turn never dies
                for w in group:
                    if not w.done.is_set():
                        w.err = exc
                        w.done.set()

    def _run(self, group: list[_Waiting]) -> None:
        v = self._st.verifier
        lead = group[0]
        items = lead.items if len(group) == 1 else \
            [it for w in group for it in w.items]
        # the kernel's marks (marshal, dispatch, device_wait) land on the
        # leading request's record; the others take them from it
        devd_spans.attach(lead.rec)
        try:
            oks = v.verify_batch(items)
            if lead.rec is not None:
                lead.rec.mark("device_wait")  # a kernel without marks
        except Exception as exc:  # noqa: BLE001
            if len(group) == 1:
                lead.err = exc
                lead.done.set()
                return
            logger.exception("merged verify of %d requests failed; "
                             "running them one by one", len(group))
            for w in group:
                self._run([w])
            return
        finally:
            devd_spans.attach(None)
        conns = len({w.conn for w in group})
        with self._st.lock:
            self._widths_run.add(_width(len(items)))
            s = self.stats
            s["programs"] += 1
            s["requests"] += len(group)
            s["lanes"] += len(items)
            s["merged_programs"] += len(group) > 1
            s["requests_max"] = max(s["requests_max"], len(group))
        at = 0
        for w in group:
            n = len(w.items)
            w.oks = [bool(b) for b in oks[at:at + n]]
            at += n
            if w.rec is not None:
                w.rec.ride(lead.rec, len(group), conns, len(items))
            w.done.set()


class _SimVerifier:
    """Transport-bench stand-in for the device kernel
    (TENDERMINT_DEVD_SIM_RATE=<sigs/s>, honored only with
    TENDERMINT_DEVD_ACCEPT_CPU=1 — never near real hardware).

    Models a pipelined device honestly: ONE worker drains dispatches
    FIFO (device compute serializes) at the configured rate, with
    verify_batch_async returning immediately — so transport/marshal
    overlap is real but simulated compute never parallelizes with
    itself. Verdicts are structural only (32/64-byte lanes pass): this
    exists to measure the IPC data plane with device time held constant,
    isolating exactly the single-shot-vs-streamed gap the r5 captures
    blamed on the serving path. Parity testing uses the real kernel."""

    def __init__(self, rate: float):
        self.rate = float(rate)
        self._q: queuelib.Queue = queuelib.Queue()
        self._stats = {"tpu_batches": 0, "tpu_sigs": 0, "cpu_sigs": 0}
        self._mtx = threading.Lock()
        threading.Thread(target=self._worker, daemon=True,
                         name="devd-simdev").start()

    def _worker(self) -> None:
        while True:
            n, done = self._q.get()
            time.sleep(n / self.rate)
            done.set()

    def verify_batch_async(self, items):
        items = list(items)
        oks = [len(it[0]) == 32 and len(it[2]) == 64 for it in items]
        done = threading.Event()
        devd_spans.mark("marshal")
        self._q.put((len(items), done))
        with self._mtx:
            self._stats["tpu_batches"] += 1
            self._stats["tpu_sigs"] += len(items)
        devd_spans.mark("dispatch")

        def resolve():
            done.wait()
            devd_spans.mark("device_wait")
            return oks

        return resolve

    def verify_batch(self, items):
        return self.verify_batch_async(items)()

    def stats(self) -> dict:
        with self._mtx:
            return dict(self._stats)


class _DevdHasher:
    """In-daemon hash backend for the real (jax) daemon: the batched
    RIPEMD-160 kernel (ops/hashing) on the held device. Dispatch rides
    jax's async execution — hash_batch_async packs and enqueues NOW and
    materializes in the resolver, so the stream handler decodes chunk
    N+1 while chunk N's compressions run."""

    def hash_batch_async(self, items, mode: str):
        import jax.numpy as jnp
        import numpy as np

        from tendermint_tpu.ops import hashing as oh

        if mode == "leaf":
            from tendermint_tpu.codec.binary import encode_bytes

            msgs = [encode_bytes(it) for it in items]
        else:
            msgs = list(items)
        if not msgs:
            return lambda: []
        words, nblocks = oh.pack_messages(msgs, little_endian=True)
        out = oh.ripemd160_words(jnp.asarray(words), jnp.asarray(nblocks))

        def resolve():
            return oh.digests_to_bytes_le(np.asarray(out))

        return resolve

    def tree_internal_nodes(self, digests):
        """Postorder internal nodes over the leaf digests, via the
        vectorized tree kernel (ops/merkle) — the tree frame payload."""
        from tendermint_tpu.ops import merkle as ops_merkle

        return ops_merkle.tree_nodes_from_leaf_digests(digests)[len(digests):]


class _SimHasher:
    """Transport-bench stand-in for the hash kernel (same
    TENDERMINT_DEVD_SIM_RATE gate as _SimVerifier): ONE FIFO worker
    computes REAL digests (crypto.hashing — byte-identical, so parity
    holds even in sim mode) and charges simulated device time at
    rate items/s, so streamed-vs-single-shot isolates the transport with
    device time held constant."""

    def __init__(self, rate: float):
        self.rate = float(rate)
        self._q: queuelib.Queue = queuelib.Queue()
        threading.Thread(target=self._worker, daemon=True,
                         name="devd-simhash").start()

    def _worker(self) -> None:
        from tendermint_tpu.codec.binary import encode_bytes
        from tendermint_tpu.crypto.hashing import ripemd160

        while True:
            items, mode, box, done = self._q.get()
            try:
                if mode == "leaf":
                    box.extend(ripemd160(encode_bytes(it)) for it in items)
                else:
                    box.extend(ripemd160(it) for it in items)
                time.sleep(len(items) / self.rate)
            finally:
                done.set()

    def hash_batch_async(self, items, mode: str):
        box: list = []
        done = threading.Event()
        self._q.put((list(items), mode, box, done))

        def resolve():
            done.wait()
            return box

        return resolve

    def tree_internal_nodes(self, digests):
        from tendermint_tpu.merkle.simple import flat_tree_from_leaf_digests

        return flat_tree_from_leaf_digests(digests).internal_nodes()


# how long a failed daemon keeps answering pings with its error before
# it exits, if no client reads it sooner
FAILED_LINGER_S = 10.0


class ClaimError(RuntimeError):
    """The claim cannot succeed: no accelerator, a refused kernel, a warm
    lane answered by the host. Final — the daemon exits non-zero."""


def _warm_batches(ed):
    """make_full(shape): a warm batch of real signatures. 64 distinct
    keys cycled across lanes: enough key diversity to exercise the comb
    pool's gather path without minutes of python keygen."""
    seeds = [bytes([5, k]) + b"\x05" * 30 for k in range(64)]
    keys = [(s, ed.public_key(s)) for s in seeds]
    base_items: list = []

    def make_full(shape: int) -> list:
        while len(base_items) < min(shape, 256):
            i = len(base_items)
            base_items.append((
                keys[i % 64][1],
                b"warm-%d" % i,
                ed.sign(keys[i % 64][0], b"warm-%d" % i),
            ))
        items = base_items[:min(shape, 256)]
        return [items[i % len(items)] for i in range(shape)]

    return make_full


def _pipelined_rate(verifier, batch: list, n_pipe: int = 6):
    """(lanes sent, sigs/s) with n_pipe batches in flight via
    verify_batch_async: serving throughput is what the daemon exists
    for, and one synchronous batch ranks kernels by dispatch latency,
    not by device rate."""
    t0 = time.time()
    resolvers = [verifier.verify_batch_async(batch) for _ in range(n_pipe)]
    for r in resolvers:
        if not all(r()):
            raise ClaimError("a valid signature was rejected in the timed pass")
    dt = time.time() - t0
    lanes = n_pipe * len(batch)
    return lanes, (lanes / dt if dt > 0 else 0.0)


def _claim(st: _DaemonState, *, accept_cpu: bool,
           warm_shapes: tuple[int, ...]) -> None:
    """Initialise the device in THIS process, warm the kernels, check
    that the device (and only the device) answered, flip to serving.
    Raises on anything less."""
    from tendermint_tpu.jitcache import enable as enable_cache
    from tendermint_tpu.ops import gateway

    st.status = "claiming"
    t_claim = time.time()
    cache_dir = enable_cache()
    if accept_cpu:
        # a CPU daemon must never take the chip from a real one
        gateway.pin_jax_cpu()
    import jax

    devs = jax.devices()  # answers or raises: the chip is attached
    platform = devs[0].platform
    if platform == "cpu" and not accept_cpu:
        raise ClaimError(
            "no accelerator: JAX initialised the cpu backend (set "
            "TENDERMINT_DEVD_ACCEPT_CPU=1 to serve it knowingly)"
        )
    st.device_kind = devs[0].device_kind
    st.device_count = len(devs)
    st.device_ids = [int(d.id) for d in devs]
    gateway.set_platform(platform)
    on_tpu = platform == "tpu"
    logger.info(
        "device initialised: %s %s x%d; compile cache %s",
        platform, st.device_kind, st.device_count, cache_dir,
    )
    # kernel choice: explicit TENDERMINT_DEVD_KERNEL wins; on TPU
    # hardware, bake off the comb kernel against the f32p ladder at claim
    # time and serve the measured winner (naming the direct kernel also
    # keeps the gateway default from routing the daemon's own verifier
    # back through devd)
    env_k = os.environ.get("TENDERMINT_DEVD_KERNEL", "")
    if env_k:
        candidates = [env_k]
    elif on_tpu:
        candidates = ["comb", "f32p"]
    else:
        candidates = ["f32"]
    st.status = "warming"
    from tendermint_tpu.crypto import ed25519 as ed

    make_full = _warm_batches(ed)
    report: dict = {}
    verifier = None
    best: tuple[float, str] | None = None
    for kname in candidates:
        # the owner of the chip never answers from the host
        v = gateway.Verifier(min_tpu_batch=1, use_tpu=True,
                             host_fallback=False, kernel=kname)
        if not warm_shapes:
            # warming disabled (TENDERMINT_DEVD_WARM=""): serve the
            # first candidate unwarmed
            if verifier is None:
                verifier = v
                best = (0.0, kname)
            continue
        krep: dict = {"warm_s": {}}
        report[kname] = krep
        sent = 0
        for shape in warm_shapes:
            t0 = time.time()
            if not all(v.verify_batch(make_full(shape))):
                raise ClaimError(
                    f"warm verify rejected a valid signature: kernel "
                    f"{kname} shape {shape}"
                )
            sent += shape
            krep["warm_s"][str(shape)] = round(time.time() - t0, 2)
            logger.info(
                "kernel %s warmed shape %d in %.1fs",
                kname, shape, time.time() - t0,
            )
            if shape not in st.warmed:
                st.warmed.append(shape)
        # timed steady-state pass at the LARGEST shape. Two untimed
        # passes first: with the comb kernel's default second-sight
        # policy the first pass at a shape may still route lanes to the
        # ladder and the second pays table builds + compile — neither
        # may land inside the timed region or the bake-off picks the
        # wrong winner.
        full = make_full(max(warm_shapes))
        t0 = time.time()
        for _ in range(2):
            v.verify_batch(full)
            sent += len(full)
        krep["steady_s"] = round(time.time() - t0, 2)
        lanes, rate = _pipelined_rate(v, full)
        sent += lanes
        krep["sigs_per_sec"] = round(rate, 1)
        logger.info(
            "kernel %s: %.0f sigs/s sustained (6 x %d pipelined)",
            kname, rate, len(full),
        )
        # the verdicts alone prove nothing about WHO computed them: the
        # counters must show every lane on the device and none on the host
        vs = v.stats()
        if vs["cpu_sigs"] or vs["tpu_sigs"] < sent:
            raise ClaimError(
                f"kernel {kname}: warm-up sent {sent} lanes, the device "
                f"counted {vs['tpu_sigs']} and the host {vs['cpu_sigs']}"
            )
        kernel_mod = v._kernel_module()
        if hasattr(kernel_mod, "built_interpret_modes"):  # a Pallas kernel
            modes = kernel_mod.built_interpret_modes()
            krep["interpret"] = modes
            if on_tpu and modes != [False]:
                raise ClaimError(
                    f"kernel {kname} was built with interpret={modes} on a TPU"
                )
        if best is None or rate > best[0]:
            best = (rate, kname)
            verifier = v
    logger.info("serving kernel: %s", best[1])
    chunk_rates: dict = {}
    if not os.environ.get("TENDERMINT_DEVD_CHUNK") and warm_shapes:
        # claim-time chunk-width bake-off, same pipelined machinery as
        # the kernel one: among widths the warm set covers, serve the
        # SMALLEST whose sustained pipelined rate is within 10% of the
        # best — finer chunks overlap socket deserialize with device
        # compute better, so ties break toward granularity
        top = max(warm_shapes)
        cands = sorted({c for c in (1024, 2048, 4096) if c <= top} or {top})
        rates: list[tuple[int, float]] = []
        for width in cands:
            batch = make_full(width)
            verifier.verify_batch(batch)  # shape warm, off-clock
            rates.append((width, _pipelined_rate(verifier, batch)[1]))
            chunk_rates[str(width)] = round(rates[-1][1], 1)
            logger.info("chunk %d: %.0f sigs/s pipelined", *rates[-1])
        best_rate = max(r for _, r in rates)
        st.stream_chunk = next(w for w, r in rates if r >= 0.9 * best_rate)
        logger.info("stream chunk width: %d", st.stream_chunk)
        if verifier.stats()["cpu_sigs"]:
            raise ClaimError("the chunk bake-off was answered by the host")
    # an open population (ops/ed25519_comb: more keys than pool slots)
    # misses inside somebody's wait all day: every program a miss can
    # need runs here once, at every bucket it can have, and the claim
    # says so. A daemon whose status shows no `miss_programs_s` compiles
    # them at first use.
    miss_s: dict = {}
    if best[1] == "comb":
        from tendermint_tpu.ops import ed25519_comb as comb

        if comb.open_population():
            miss_s = comb.compile_miss_programs(make_full)
            logger.info("open population: miss programs ready %s", miss_s)
            if verifier.stats()["cpu_sigs"]:
                raise ClaimError("a miss program was answered by the host")
    st.claim = {
        "cache_dir": cache_dir,
        "kernels": report,
        "served": best[1],
        "chunk_rates": chunk_rates,
        "miss_programs_s": miss_s,
        "claim_s": round(time.time() - t_claim, 2),
    }
    with st.lock:
        st.platform = platform
        st.verifier = verifier
        # hash plane rides the same held device; compiles lazily on the
        # first hash op (part widths repeat, so the jit cache hits from
        # then on)
        st.hasher = _DevdHasher()
        st.status = "serving"
    logger.info(
        "device held (%s) after %.1fs; serving", st.platform,
        st.claim["claim_s"],
    )


def _device_loop(st: _DaemonState, *, accept_cpu: bool,
                 warm_shapes: tuple[int, ...]) -> None:
    """Claim the device, warm kernels, flip state to serving — once. A
    failure is final (see the module docstring)."""
    sim_rate = float(os.environ.get("TENDERMINT_DEVD_SIM_RATE", "0") or 0)
    if sim_rate > 0:
        # accept_cpu enforcement lives in serve() — a SystemExit raised
        # here, inside a daemon thread, would be swallowed silently
        # pure-python daemon: no jax, no device, instant startup — exists
        # for transport tests that need device time held constant
        with st.lock:
            st.platform = "cpu"
            st.verifier = _SimVerifier(sim_rate)
            st.hasher = _SimHasher(sim_rate)
            st.status = "serving"
        logger.info("sim device (%.0f sigs/s); serving", sim_rate)
        return
    try:
        _claim(st, accept_cpu=accept_cpu, warm_shapes=warm_shapes)
    except BaseException as exc:  # noqa: BLE001 — any failure is final
        logger.exception("claim failed; the daemon exits")
        st.error = f"{type(exc).__name__}: {exc}"
        st.status = "failed"
        st.failed_seen.wait(FAILED_LINGER_S)
        st.stop.set()


def _comb_pool_stats() -> dict | None:
    """Residency of the comb kernel's per-validator table pool, when
    that kernel has run in this daemon (status op)."""
    comb = sys.modules.get("tendermint_tpu.ops.ed25519_comb")
    if comb is None or not comb._default_pool:
        return None
    pool = comb.default_pool()
    resident = len(pool._lru)
    return {"capacity": pool.capacity, "resident_keys": resident,
            "resident_bytes": resident * comb.SLOT_BYTES, "open": pool.open,
            **pool.stats}


def _stream_depth() -> int:
    try:
        return max(2, int(os.environ.get("TENDERMINT_DEVD_STREAM_DEPTH", "4")))
    except ValueError:  # serve() validates; stay serving if it didn't run
        return 4


def _handle_verify_stream(conn: socket.socket, st: _DaemonState,
                          req: dict, conn_id: int = 0) -> bool:
    """Serve one verify_stream request: read chunk frames off the socket,
    dispatch each to the kernel as it decodes (verify_batch_async), and
    stream verdict frames back in order from a sender thread — so chunk
    N+1 deserializes while chunk N is in the kernel. Returns True when
    the connection stays usable (all chunks answered), False when the
    stream aborted (error frame sent; caller closes the connection)."""
    n_chunks = int(req.get("chunks", 0))
    v = st.verifier
    if v is None or n_chunks < 0:
        _send_error_frame(
            conn, 0xFFFFFFFF,
            f"device not held (status: {st.status})" if v is None
            else f"bad chunk count {n_chunks}",
        )
        return False
    with st.lock:
        st.stream["streams"] += 1
    return _serve_stream(
        conn, st, st.stream, n_chunks,
        _unpack_chunk, v.verify_batch_async, _send_result_frame,
        record=(st.spans, conn_id, req.get("rid", "")),
    )


def _handle_hash_stream(conn: socket.socket, st: _DaemonState,
                        req: dict) -> bool:
    """Serve one hash_stream request on the shared stream core: hash
    chunk frames decode as they arrive, each dispatches to the batched
    RIPEMD-160 kernel, digest frames stream back per chunk in order.
    With "tree": true the leaf digests accumulate (in chunk order,
    through the sender thread) and ONE tree frame with every internal
    node follows the last digest frame — proofs come free host-side."""
    n_chunks = int(req.get("chunks", 0))
    mode = req.get("mode", "part")
    want_tree = bool(req.get("tree"))
    h = st.hasher
    if h is None or n_chunks < 0 or mode not in HASH_MODES:
        _send_error_frame(
            conn, 0xFFFFFFFF,
            f"device not held (status: {st.status})" if h is None
            else (f"bad chunk count {n_chunks}" if n_chunks < 0
                  else f"bad hash mode {mode!r}"),
        )
        return False
    with st.lock:
        st.hash_stream["streams"] += 1
    leaves: list = []
    ok = _serve_stream(
        conn, st, st.hash_stream, n_chunks,
        _unpack_hash_chunk, lambda items: h.hash_batch_async(items, mode),
        _send_digest_frame,
        on_result=(leaves.extend if want_tree else None),
    )
    if not ok:
        return False
    if want_tree:
        try:
            nodes = h.tree_internal_nodes(leaves) if len(leaves) > 1 else []
            _send_tree_frame(conn, nodes)
            with st.lock:
                st.hash_stream["trees"] += 1
        except Exception as exc:  # noqa: BLE001 — tree build/send died
            logger.exception("hash tree build failed")
            try:
                _send_error_frame(conn, n_chunks, f"{type(exc).__name__}: {exc}")
            except Exception:
                pass
            with st.lock:
                st.hash_stream["errors"] += 1
            return False
    return True


def _serve_stream(conn: socket.socket, st: _DaemonState, gauges: dict,
                  n_chunks: int, unpack, dispatch, send_result,
                  on_result=None, record=None) -> bool:
    """The chunked-stream serving core shared by verify_stream and
    hash_stream: bounded in-flight dispatch, in-order result frames from
    a sender thread, error-frame-then-close on any malformed frame.
    `gauges` is the st-owned counter dict (st.stream / st.hash_stream —
    same keys); `dispatch(items)` returns a zero-arg resolver;
    `send_result(conn, idx, result)` frames one chunk's result;
    `on_result(result)` (optional) observes results in chunk order from
    the sender thread; `record` = (ring, connection id, the stream's rid)
    makes every chunk one record of the ring (the verify plane): opened
    on this thread, handed to the sender thread with the chunk. Returns
    True when the connection stays usable."""
    ring, conn_id, rid = record or (None, 0, "")
    depth = threading.Semaphore(_stream_depth())
    results: queuelib.Queue = queuelib.Queue()
    send_ok = threading.Event()
    send_ok.set()

    def sender() -> None:
        while True:
            entry = results.get()
            if entry is None:
                return
            idx, resolver_or_err, n, t_disp, rec = entry
            devd_spans.attach(rec)
            try:
                if isinstance(resolver_or_err, str):
                    _send_error_frame(conn, idx, resolver_or_err)
                    with st.lock:
                        gauges["errors"] += 1
                    send_ok.clear()
                    return
                counted = False
                res = resolver_or_err()
                if rec is not None:
                    rec.mark("device_wait")  # a kernel without marks
                dt_ms = (time.time() - t_disp) * 1000.0
                with st.lock:
                    s = gauges
                    s["inflight"] -= 1
                    counted = True
                    s["chunks"] += 1
                    s["lanes"] += n
                    s["chunk_device_ms_last"] = round(dt_ms, 3)
                    s["chunk_device_ms_avg"] = round(
                        0.8 * s["chunk_device_ms_avg"] + 0.2 * dt_ms, 3
                    ) if s["chunk_device_ms_avg"] else round(dt_ms, 3)
                if on_result is not None:
                    on_result(res)
                send_result(conn, idx, res)
                if rec is not None:
                    ring.finish(rec)
                    rec = None
            except Exception as exc:  # noqa: BLE001 — resolve/send died
                logger.exception("stream chunk %d failed", idx)
                try:
                    _send_error_frame(conn, idx, f"{type(exc).__name__}: {exc}")
                except Exception:
                    pass
                with st.lock:
                    gauges["errors"] += 1
                    # decrement exactly once per dispatched chunk: the
                    # success path may have counted it before the send
                    # died (a post-send failure must not double-count)
                    if not isinstance(resolver_or_err, str) and not counted:
                        gauges["inflight"] -= 1
                send_ok.clear()
                return
            finally:
                if ring is not None:
                    ring.drop(rec)  # a chunk that failed leaves no record
                depth.release()

    send_thread = threading.Thread(target=sender, daemon=True,
                                   name="devd-stream-send")
    send_thread.start()

    def acquire_slot() -> bool:
        """Bound in-flight device work WITHOUT deadlocking on a dead
        sender: give up as soon as the stream is known broken."""
        while send_ok.is_set():
            if depth.acquire(timeout=0.5):
                return True
        return False

    aborted = False
    try:
        for idx in range(n_chunks):
            rec = None
            try:
                size = _recv_frame_len(conn)
                if ring is not None:
                    rec = ring.begin(conn_id)
                payload = _recv_exact(conn, size)
                items = unpack(payload)
            except (ConnectionError, EOFError):
                aborted = True
                break
            except Exception as exc:  # noqa: BLE001 — malformed frame:
                # answer with an error frame, never hang the client
                if acquire_slot():
                    results.put((idx, f"malformed chunk: {exc}", 0, 0.0, None))
                aborted = True
                break
            if rec is not None:
                ring.decoded(rec, "verify_stream", len(items), rid)
            if not acquire_slot():
                aborted = True
                break
            try:
                resolver = dispatch(items)
            except Exception as exc:  # noqa: BLE001 — dispatch failed
                results.put((idx, f"{type(exc).__name__}: {exc}", 0, 0.0, None))
                aborted = True
                break
            if rec is not None:
                rec.mark("dispatch")
            with st.lock:
                s = gauges
                s["bytes_framed"] += len(payload)
                s["inflight"] += 1
                s["inflight_max"] = max(s["inflight_max"], s["inflight"])
            # the record goes on with the chunk: the sender thread ends
            # its last two phases
            devd_spans.attach(None)
            results.put((idx, resolver, len(items), time.time(), rec))
            rec = None
    finally:
        if ring is not None:
            ring.drop(rec)
        results.put(None)
        send_thread.join()
        # stats hygiene on abort: entries the dead sender never resolved
        # must not leave the in-flight gauge elevated forever
        leaked = 0
        while True:
            try:
                entry = results.get_nowait()
            except queuelib.Empty:
                break
            if entry is not None and not isinstance(entry[1], str):
                leaked += 1
                if entry[4] is not None:
                    ring.drop(entry[4])
        if leaked:
            with st.lock:
                gauges["inflight"] -= leaked
    return not aborted and send_ok.is_set()


def _handle_conn(conn: socket.socket, st: _DaemonState) -> None:
    ring = st.spans
    conn_id = next(st.conn_ids)
    rec = None
    with st.lock:
        st.conns_open += 1
        st.conns_open_max = max(st.conns_open_max, st.conns_open)
    try:
        while True:
            try:
                size = _recv_frame_len(conn)
                # t_recv0: the header is in hand, the idle wait is over
                rec = ring.begin(conn_id)
                req = pickle.loads(_recv_exact(conn, size))
            except (ConnectionError, EOFError):
                return
            op = req.get("op")

            def held_stats() -> dict:
                with st.lock:
                    return st.verifier.stats() if st.verifier else {}

            try:
                if op in ("ping", "status"):
                    rep = {
                        "ok": True,
                        "platform": st.platform,
                        "held": st.verifier is not None,
                        "status": st.status,
                        "warmed": list(st.warmed),
                        "uptime_s": round(time.time() - st.started, 1),
                        "stats": held_stats(),
                        "pid": os.getpid(),
                        "stream_chunk": st.stream_chunk,
                        # what jax.devices() told the owner — the only
                        # process that may ask
                        "device_kind": st.device_kind,
                        "device_count": st.device_count,
                        "device_ids": list(st.device_ids),
                        # the chip the LAUNCHER bound this daemon to
                        # (libtpu's own variable; None = all it finds)
                        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
                        "error": st.error,
                        "conns_open": st.conns_open,
                    }
                    if op == "status":
                        # the claim's own account: cache dir in use,
                        # seconds per kernel and warm shape, bake-off
                        # rates, kernel served, interpret= of a Pallas one
                        rep["claim"] = dict(st.claim)
                        rep["comb_pool"] = _comb_pool_stats()
                        # the serving-path bottleneck, measurable in
                        # production: chunks in flight, bytes framed,
                        # per-chunk device latency (ISSUE 1 satellite;
                        # hash plane ISSUE 2)
                        rep["stream"] = st.stream_stats()
                        rep["hash_stream"] = st.hash_stream_stats()
                        rep["stream_depth"] = _stream_depth()
                        # the ring of per-call records: its size and how
                        # many it has ever held (past the size it wrapped)
                        rep["spans"] = ring.stats()
                        rep["profiling"] = st.profile.active()
                        # how the single-shot verify requests rode the
                        # device: programs, requests, lanes, merged ones
                        with st.lock:
                            rep["merge"] = dict(st.merger.stats)
                            rep["conns_open_max"] = st.conns_open_max
                    _send_frame(conn, rep)
                    if st.status == "failed":
                        st.failed_seen.set()
                elif op == "verify_stream":
                    ring.drop(rec)  # the header; each chunk is a record
                    rec = None
                    if not _handle_verify_stream(conn, st, req, conn_id):
                        return  # stream aborted; framing is untrustworthy
                elif op == "hash_stream":
                    if not _handle_hash_stream(conn, st, req):
                        return  # stream aborted; framing is untrustworthy
                elif op == "hash":
                    # single-shot hash: one pickle frame each way — what
                    # small batches ride (stream setup loses below
                    # TENDERMINT_DEVD_STREAM_MIN) and the baseline the
                    # hash-stream bench row measures against
                    h = st.hasher
                    mode = req.get("mode", "part")
                    if h is None:
                        _send_frame(conn, {
                            "ok": False,
                            "error": f"device not held (status: {st.status})",
                        })
                    elif mode not in HASH_MODES:
                        _send_frame(conn, {
                            "ok": False, "error": f"bad hash mode {mode!r}",
                        })
                    else:
                        items = [bytes(b) for b in req.get("items", [])]
                        digests = h.hash_batch_async(items, mode)()
                        rep = {"ok": True, "digests": digests}
                        if req.get("tree"):
                            rep["nodes"] = (
                                h.tree_internal_nodes(digests)
                                if len(digests) > 1 else []
                            )
                        with st.lock:
                            st.hash_stream["single_batches"] += 1
                            st.hash_stream["single_lanes"] += len(items)
                        _send_frame(conn, rep)
                elif op == "verify":
                    v = st.verifier
                    if v is None:
                        _send_frame(conn, {
                            "ok": False,
                            "error": f"device not held (status: {st.status})",
                        })
                    else:
                        items = req["items"]
                        ring.decoded(rec, op, len(items), req.get("rid", ""))
                        # with whatever else is waiting, as one program
                        oks = st.merger.verify(items, rec, conn_id)
                        _send_frame(conn, {
                            "ok": True, "results": oks,
                            "svc_ns": rec.service_ns(),
                        })
                        ring.finish(rec)
                elif op == "agg":
                    # aggregate-commit dual-scalar-mul lanes
                    # (ops/ed25519.dsm_batch; docs/upgrade.md): terms are
                    # (a, (px,py), b, (qx,qy)) python-int tuples, the
                    # reply the per-lane affine points. Rides the held
                    # device via the int32 field module directly — the
                    # only one with the dsm ladder.
                    if st.verifier is None:
                        _send_frame(conn, {
                            "ok": False,
                            "error": f"device not held (status: {st.status})",
                        })
                    else:
                        from tendermint_tpu.ops import ed25519 as _ops_ed

                        terms = [tuple(t) for t in req.get("items", [])]
                        ring.decoded(rec, op, len(terms), req.get("rid", ""))
                        # dsm_batch marshals, dispatches and reads back in
                        # one call: its whole time stands as device_wait
                        rec.mark("dispatch")
                        points = _ops_ed.dsm_batch(terms)
                        rec.mark("device_wait")
                        _send_frame(conn, {"ok": True, "points": points,
                                           "svc_ns": rec.service_ns()})
                        ring.finish(rec)
                elif op == "stats":
                    _send_frame(conn, {
                        "ok": True,
                        "stats": held_stats(),
                        "stream": st.stream_stats(),
                        "hash_stream": st.hash_stream_stats(),
                    })
                elif op == "spans":
                    # the ring of per-call records (devd_spans.FIELDS),
                    # oldest first: `since_ns` keeps those received at or
                    # after it, `last` the newest that many
                    last = req.get("last")
                    _send_frame(conn, {
                        "ok": True, "fields": list(devd_spans.FIELDS),
                        "records": ring.rows(
                            int(req.get("since_ns", 0) or 0),
                            None if last is None else int(last)),
                        **ring.stats(),
                    })
                elif op == "profile":
                    # jax.profiler from inside: `start` (dir, max_calls;
                    # stops by itself after that many verifier calls, on
                    # a thread of its own) and `stop`. Errors are replies.
                    action = req.get("action")
                    if action == "start" and req.get("dir"):
                        rep = st.profile.start(
                            str(req["dir"]), int(req.get("max_calls", 0) or 0))
                    elif action == "stop":
                        rep = st.profile.stop()
                    else:
                        rep = {"ok": False, "error":
                               f"profile: bad request {action!r} "
                               "(start needs dir; or stop)"}
                    _send_frame(conn, rep)
                elif op == "shutdown":
                    _send_frame(conn, {"ok": True})
                    st.stop.set()
                    return
                else:
                    _send_frame(conn, {"ok": False, "error": f"unknown op {op!r}"})
            except Exception as exc:  # noqa: BLE001 — report, keep serving
                logger.exception("request failed")
                try:
                    _send_frame(conn, {"ok": False, "error": f"{type(exc).__name__}: {exc}"})
                except Exception:
                    return
            finally:
                # ping, status, hash and whatever failed leave no record
                ring.drop(rec)
                rec = None
    finally:
        ring.drop(rec)
        with st.lock:
            st.conns_open -= 1
        try:
            conn.close()
        except Exception:
            pass


def serve(path: str | None = None) -> None:
    """Run the daemon (blocking). Env knobs:
    TENDERMINT_DEVD_SOCK          socket path (default /tmp/tendermint-devd.sock)
    TENDERMINT_DEVD_ACCEPT_CPU=1  serve the CPU backend (tests / no hardware)
    TENDERMINT_DEVD_WARM          comma-separated warm shapes (default 1024,4096,8192)
    TENDERMINT_DEVD_KERNEL        pin the served kernel (skips the claim-time
                                  comb-vs-f32p bake-off; any gateway.KERNELS
                                  name except "devd")
    TENDERMINT_DEVD_EXIT_ON_TERM=1  honor SIGTERM (default: ignore — device
                                  discipline); it then stops as the shutdown
                                  op does, records written out
    TENDERMINT_DEVD_CHUNK         pin the streamed chunk width (skips the
                                  claim-time width bake-off; clients pin
                                  their framing with the same var)
    TENDERMINT_DEVD_STREAM_DEPTH  max chunks in flight per stream (default 4)
    TENDERMINT_DEVD_SIM_RATE      serve a SIMULATED device at this sigs/s —
                                  transport benches only; requires ACCEPT_CPU=1
    """
    path = path or sock_path()
    env_k = os.environ.get("TENDERMINT_DEVD_KERNEL", "")
    if env_k:
        from tendermint_tpu.ops.gateway import KERNELS

        # fail fast at startup: inside the claim loop a bad name would be
        # swallowed by the retry handler and the daemon would spin forever
        if env_k not in KERNELS or env_k == "devd":
            raise SystemExit(
                f"TENDERMINT_DEVD_KERNEL={env_k!r}: expected one of "
                f"{sorted(k for k in KERNELS if k != 'devd')}"
            )
    accept_cpu = os.environ.get("TENDERMINT_DEVD_ACCEPT_CPU", "") == "1"
    # fail fast at startup on the remaining env knobs too: inside the
    # device thread a raise would be swallowed (threading ignores
    # SystemExit off the main thread) and the daemon would sit in
    # "starting" forever
    if float(os.environ.get("TENDERMINT_DEVD_SIM_RATE", "0") or 0) > 0 \
            and not accept_cpu:
        raise SystemExit(
            "TENDERMINT_DEVD_SIM_RATE requires TENDERMINT_DEVD_ACCEPT_CPU=1 "
            "(the sim verifier must never stand in front of real hardware)"
        )
    depth_env = os.environ.get("TENDERMINT_DEVD_STREAM_DEPTH", "")
    if depth_env:
        try:
            int(depth_env)
        except ValueError:
            raise SystemExit(
                f"TENDERMINT_DEVD_STREAM_DEPTH={depth_env!r}: expected an int"
            ) from None
    warm = tuple(
        int(x) for x in os.environ.get(
            "TENDERMINT_DEVD_WARM", "1024,4096,8192"
        ).split(",") if x
    )

    exit_on_term = os.environ.get("TENDERMINT_DEVD_EXIT_ON_TERM", "") == "1"
    if not exit_on_term:
        def _ignore(signum, frame):
            logger.warning(
                "ignoring signal %d: the device owner outlives its clients; "
                "use the shutdown op (or TENDERMINT_DEVD_EXIT_ON_TERM=1)",
                signum,
            )
        signal.signal(signal.SIGTERM, _ignore)
        signal.signal(signal.SIGINT, _ignore)

    # Bind first: refuse to start a second daemon on a live socket.
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    if os.path.exists(path):
        probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        probe.settimeout(1.0)
        try:
            probe.connect(path)
            raise SystemExit(f"devd already serving on {path}")
        except (ConnectionRefusedError, socket.timeout, FileNotFoundError):
            os.unlink(path)  # stale socket from a dead daemon
        finally:
            probe.close()
    srv.bind(path)
    os.chmod(path, 0o600)
    srv.listen(64)
    srv.settimeout(1.0)

    st = _DaemonState()
    if exit_on_term:
        # leave through the same door as the shutdown op, so that the
        # records are written out; closing the listener wakes accept()
        def _term(signum, frame):
            st.stop.set()
            srv.close()
        try:
            signal.signal(signal.SIGTERM, _term)
        except ValueError:  # serve() off the main thread: default action
            pass
    threading.Thread(
        target=_device_loop, args=(st,),
        kwargs=dict(accept_cpu=accept_cpu, warm_shapes=warm),
        daemon=True, name="devd-device",
    ).start()

    logger.info("devd listening on %s (pid %d)", path, os.getpid())
    try:
        while not st.stop.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                if st.stop.is_set():
                    break
                raise
            threading.Thread(
                target=_handle_conn, args=(conn, st), daemon=True
            ).start()
    finally:
        srv.close()
        try:
            os.unlink(path)
        except OSError:
            pass
        # what an operator reads after a restart: the ring of per-call
        # records, beside the socket (a running profile is written first)
        if st.profile.active():
            st.profile.stop()
        spans_path = st.spans.dump(
            devd_spans.dump_path(path), pid=os.getpid(),
            device_kind=st.device_kind, platform=st.platform,
            merge=dict(st.merger.stats), conns_open_max=st.conns_open_max,
        )
        logger.info("devd stopped; %d call records in %s",
                    min(st.spans.count, st.spans.size), spans_path)
        # an open population's pool: its log of batches beside the records
        comb = sys.modules.get("tendermint_tpu.ops.ed25519_comb")
        if comb is not None and comb._default_pool:
            comb.default_pool().dump_log(
                devd_spans.dump_path(path, ".pool.jsonl"))
    if st.error is not None:
        raise SystemExit(f"devd: claim failed: {st.error}")


# -- client -------------------------------------------------------------------


class DevdError(Exception):
    pass


# Sanctioned fault-injection point (ops/faults.py): when set, every NEW
# client connection passes through the wrapper (a socket-like proxy that
# injects scheduled faults). Production leaves it None; chaos tests and
# benches install it so the UNMODIFIED client/gateway triage paths are
# what gets exercised — no monkeypatching of internals.
_socket_wrapper = None


def set_socket_wrapper(wrapper) -> None:
    """Install (or clear, with None) the connection-factory wrapper
    applied by DevdClient._fresh. See ops/faults.install_client_faults."""
    global _socket_wrapper
    _socket_wrapper = wrapper


# -- client latency distributions (round 11) ----------------------------------
#
# The counters above say HOW MUCH rode each transport; these histograms
# say how LONG it took — the distributions the pipelining/sharding PRs
# will be judged against (docs/observability.md). Process-wide (the devd
# client is process-global), labeled by plane: op="verify" | "hash".

_hist_cache: dict = {}


def _latency_hists():
    """(per-chunk stream wait, single-shot round trip) histograms off
    the CURRENT default telemetry registry — re-fetched when tests swap
    the registry, cached otherwise so the hot path pays a dict probe."""
    from tendermint_tpu.libs import telemetry

    reg = telemetry.default_registry()
    if _hist_cache.get("reg") is not reg:
        _hist_cache["chunk"] = reg.histogram(
            "devd_stream_chunk_seconds",
            "per-chunk result wait on an active devd stream (writer "
            "overlap means this is the residual, not the full RTT)",
            labelnames=("op",),
        )
        _hist_cache["single"] = reg.histogram(
            "devd_single_shot_seconds",
            "single-shot devd pickle round trip (whole batch)",
            labelnames=("op",),
        )
        _hist_cache["ipc"] = reg.histogram(
            "devd_single_shot_ipc_seconds",
            "single-shot devd round trip LESS the daemon's own service "
            "time (the reply's svc_ns): the socket both ways, the "
            "request's pickle, the reply's encoding and decoding, the "
            "scheduler",
            labelnames=("op",),
        )
        _hist_cache["reg"] = reg
    return _hist_cache["chunk"], _hist_cache["single"], _hist_cache["ipc"]


# what single-shot calls made ON THIS THREAD have spent in IPC so far:
# the consensus receive routine reads it before and after a wait for
# verdicts (consensus/state.py: the height trace's verify_ipc_s)
_ipc_tls = threading.local()


def thread_ipc_ns() -> int:
    return getattr(_ipc_tls, "ns", 0)


def thread_batch_ipc_ns() -> int:
    """The IPC of pipelined batches whose verdicts this thread was the
    first to take (ops/gateway: a batch resolves on a thread of its own,
    and the thread that pops its first lane books its IPC here): the
    height trace's verify_batch_ipc_s."""
    return getattr(_ipc_tls, "batch_ns", 0)


def note_batch_ipc_ns(ns: int) -> None:
    _ipc_tls.batch_ns = thread_batch_ipc_ns() + int(ns)


def _observe_single(op: str, t0: float, rep: dict) -> None:
    """One single-shot round trip: its whole time, and (where the daemon
    says how long it held the request: an older one does not) the rest,
    which is the IPC of that call."""
    rtt = time.perf_counter() - t0
    _chunk, single, ipc_hist = _latency_hists()
    single.labels(op=op).observe(rtt)
    svc_ns = rep.get("svc_ns") if isinstance(rep, dict) else None
    if svc_ns is not None:
        ipc = max(0.0, rtt - svc_ns / 1e9)
        ipc_hist.labels(op=op).observe(ipc)
        _ipc_tls.ns = thread_ipc_ns() + int(ipc * 1e9)


_rid_counter = itertools.count(1)
# who asks, and why: a request's rid is `<client>-<why>-<counter>`, and
# the daemon's record of the call parses `node` and `why` back out of it
# (devd_spans.parse_rid). A node names its process (its moniker); any
# other process is `p<pid>`. The call site names the purpose on its own
# thread (`asking`: gate, vote, commit, block, sync; docs/device-daemon.md);
# what no call site named is `warm`: set-up traffic.
_client_name = f"p{os.getpid()}"
_ask = threading.local()


def set_client_name(name: str) -> None:
    global _client_name
    _client_name = str(name).strip() or f"p{os.getpid()}"


class asking:
    """`with devd.asking("vote"): ...`: every request this thread sends
    meanwhile names that purpose (nests; the outer one returns after)."""

    __slots__ = ("why", "_prev")

    def __init__(self, why: str):
        self.why = why

    def __enter__(self):
        self._prev = getattr(_ask, "why", None)
        _ask.why = self.why
        return self

    def __exit__(self, *exc) -> None:
        _ask.why = self._prev


def current_why() -> str | None:
    return getattr(_ask, "why", None)


def take_rid() -> str:
    """The rid of this thread's last request since the last take ("" if
    none went out: the call was answered on the host)."""
    rid = getattr(_ask, "rid", "")
    _ask.rid = ""
    return rid


def _next_rid() -> str:
    """The client's name for one request: the daemon's record of the call
    carries it."""
    rid = (f"{_client_name}-{getattr(_ask, 'why', None) or 'warm'}"
           f"-{next(_rid_counter)}")
    _ask.rid = rid
    return rid


class DevdClient:
    """Client for the device daemon. verify_batch is synchronous;
    verify_batch_async sends on a pooled connection and returns a
    zero-arg resolver (the gateway's pipelining contract) — concurrent
    in-flight requests each ride their own connection, and the daemon
    serves connections in parallel, so the device queue stays full.

    verify_stream / verify_stream_async ride the chunked streaming
    protocol (module docstring): a writer thread packs and sends
    fixed-width chunk frames while the daemon verifies earlier chunks,
    and verdicts stream back per chunk — host marshal, IPC, and device
    compute all overlap instead of paying one monolithic round trip.

    A request that fails on a POOLED connection retries once on a fresh
    one: pooled sockets go stale whenever the daemon restarts, and a
    client must survive that without its caller seeing the flap.

    Deadline budgets (round 8): the single flat io_timeout is now only
    the default for three per-phase budgets — `connect` (dial), `claim`
    (control-plane ops: ping/status/stats/shutdown and stream headers),
    and `stream` (each frame read/write on an active stream). Data-plane
    single-shot verify/hash keep the full io budget (a first batch may
    legitimately sit behind a minutes-long kernel compile); everything
    else can and should fail faster. Env overrides:
    TENDERMINT_DEVD_CONNECT_TIMEOUT_S / _CLAIM_TIMEOUT_S /
    _STREAM_TIMEOUT_S."""

    def __init__(self, path: str | None = None,
                 connect_timeout: float | None = None,
                 io_timeout: float = 300.0, claim_timeout: float | None = None,
                 stream_timeout: float | None = None):
        self.path = path or sock_path()
        # env tunes only the DEFAULTS — an explicit constructor arg
        # always wins (devd.available builds its probe client with
        # connect_timeout=1.0 precisely so the breaker's inline health
        # probe stays bounded ~1 s; an operator's env knob must not
        # silently un-bound the verify hot path through it)
        self.connect_timeout = connect_timeout if connect_timeout is not None \
            else _env_timeout("TENDERMINT_DEVD_CONNECT_TIMEOUT_S", 2.0)
        self.io_timeout = io_timeout
        self.claim_timeout = claim_timeout if claim_timeout is not None \
            else _env_timeout("TENDERMINT_DEVD_CLAIM_TIMEOUT_S", io_timeout)
        self.stream_timeout = stream_timeout if stream_timeout is not None \
            else _env_timeout("TENDERMINT_DEVD_STREAM_TIMEOUT_S", io_timeout)
        self._pool: list[socket.socket] = []
        self._mtx = threading.Lock()
        self._adv_chunk: int | None = None  # daemon-advertised width
        # reconnects is the TOTAL; the labeled pair splits it by where
        # the stale socket surfaced — at first use of a pooled conn
        # (reconnects_connect: daemon restarted between requests) vs
        # mid-exchange (reconnects_midstream: it died under an active
        # request/stream) — so chaos tests can assert WHICH path fired
        self._stream_stats = {
            "stream_batches": 0, "stream_chunks_out": 0,
            "stream_lanes": 0, "stream_bytes_out": 0, "reconnects": 0,
            "reconnects_connect": 0, "reconnects_midstream": 0,
            "writer_abandoned": 0,
        }
        # hash-plane counters, same key shape (consumers prefix; the
        # gateway Hasher folds these in as flat stream_* gauges)
        self._hash_stats = {
            "stream_batches": 0, "stream_chunks_out": 0,
            "stream_lanes": 0, "stream_bytes_out": 0, "reconnects": 0,
            "reconnects_connect": 0, "reconnects_midstream": 0,
            "writer_abandoned": 0,
            "stream_trees": 0, "single_batches": 0, "single_lanes": 0,
        }

    def _note_reconnect(self, stats: dict, where: str) -> None:
        with self._mtx:
            stats["reconnects"] += 1
            stats[f"reconnects_{where}"] += 1

    def _acquire(self) -> tuple[socket.socket, bool]:
        """(connection, was_pooled). Pooled sockets may be stale — the
        caller retries once on a fresh one when was_pooled."""
        with self._mtx:
            if self._pool:
                return self._pool.pop(), True
        return self._fresh(), False

    def _release(self, conn: socket.socket) -> None:
        with self._mtx:
            self._pool.append(conn)

    def _discard(self, conn: socket.socket) -> None:
        try:
            conn.close()
        except Exception:
            pass

    def _kill(self, conn) -> None:
        """shutdown THEN discard: a conn being abandoned mid-stream may
        have the writer thread blocked in sendall on it, and close()
        alone never wakes a syscall pinned on the same fd — shutdown
        fails it fast, so the follow-up _reap_writer join returns
        promptly instead of burning the full reap budget."""
        try:
            conn.shutdown(socket.SHUT_RDWR)
        except Exception:  # noqa: BLE001 — already dead is fine
            pass
        self._discard(conn)

    def request(self, obj, timeout: float | None = None) -> dict:
        """One pickle round trip. The read/write budget defaults to the
        CLAIM deadline (control-plane ops fail fast); data-plane ops
        that may sit behind a kernel compile pass the io budget
        explicitly (verify_batch / hash_batch)."""
        conn, pooled = self._acquire()
        while True:
            conn.settimeout(timeout if timeout is not None
                            else self.claim_timeout)
            try:
                _send_frame(conn, obj)
                rep = _recv_frame(conn)
            except Exception as exc:
                self._discard(conn)
                # retry ONLY plausibly-stale pooled sockets (the daemon
                # restarted between requests): ConnectionError/EOF. A
                # timeout is a live-but-slow daemon — resubmitting the
                # same work would double device load exactly when it is
                # saturated (and break at-most-once for non-verify ops).
                if pooled and isinstance(exc, (ConnectionError, EOFError)):
                    self._note_reconnect(self._stream_stats, "connect")
                    conn, pooled = self._fresh(), False
                    continue
                raise
            conn.settimeout(self.io_timeout)
            self._release(conn)
            return rep

    def _fresh(self) -> socket.socket:
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.settimeout(self.connect_timeout)
        conn.connect(self.path)
        conn.settimeout(self.io_timeout)
        if _socket_wrapper is not None:  # chaos harness (ops/faults.py)
            conn = _socket_wrapper(conn)
        return conn

    def ping(self, timeout: float = 5.0) -> dict:
        rep = self.request({"op": "ping"}, timeout=timeout)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "ping failed"))
        return rep

    def verify_batch(self, items) -> list[bool]:
        t0 = time.perf_counter()
        rep = self.request(
            {"op": "verify", "items": list(items), "rid": _next_rid()},
            timeout=self.io_timeout)
        _observe_single("verify", t0, rep)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "verify failed"))
        return rep["results"]

    def agg_batch(self, terms) -> list[tuple[int, int]]:
        """Aggregate-commit dual-scalar-mul lanes (the 'agg' op): terms
        as in ops/ed25519.dsm_batch; returns per-lane affine points. A
        pre-agg daemon replies 'unknown op' -> DevdError, which
        ops/devd_backend latches into its CPU-floor fallback."""
        t0 = time.perf_counter()
        rep = self.request(
            {"op": "agg", "items": [tuple(t) for t in terms],
             "rid": _next_rid()}, timeout=self.io_timeout)
        _observe_single("agg", t0, rep)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "agg failed"))
        return [tuple(p) for p in rep["points"]]

    def verify_batch_async(self, items):
        items = list(items)
        req = {"op": "verify", "items": items, "rid": _next_rid()}
        conn, pooled = self._acquire()
        try:
            _send_frame(conn, req)
        except Exception as exc:
            self._discard(conn)
            if not (pooled and isinstance(exc, (ConnectionError, EOFError))):
                raise
            self._note_reconnect(self._stream_stats, "connect")
            conn, pooled = self._fresh(), False
            try:
                _send_frame(conn, req)
            except Exception:
                self._discard(conn)
                raise

        t0 = time.perf_counter()

        def resolve() -> list[bool]:
            try:
                rep = _recv_frame(conn)
                # the whole round trip from the send, less what the daemon
                # held the request: this batch's IPC, on this thread
                _observe_single("verify_async", t0, rep)
            except Exception as exc:
                self._discard(conn)
                if pooled and isinstance(exc, (ConnectionError, EOFError)):
                    # stale pooled socket: the daemon restarted between
                    # requests — the whole batch retries on a fresh conn
                    # (timeouts deliberately do NOT retry: see request())
                    self._note_reconnect(self._stream_stats, "midstream")
                    return self.verify_batch(items)
                raise
            self._release(conn)
            if not rep.get("ok"):
                raise DevdError(rep.get("error", "verify failed"))
            return rep["results"]

        return resolve

    # -- streaming transport ------------------------------------------------

    def stream_chunk(self) -> int:
        """Chunk width for streamed submission: TENDERMINT_DEVD_CHUNK
        pins it; otherwise the daemon's claim-time-tuned width (one ping,
        cached for the client lifetime); DEFAULT_STREAM_CHUNK failing
        both."""
        try:
            env = int(os.environ.get("TENDERMINT_DEVD_CHUNK", "0") or 0)
        except ValueError:  # a typo'd env var must not kill the verify
            # hot path (gateway would latch the CPU fallback); the
            # daemon-side serve() validation is the loud failure
            logger.warning("ignoring malformed TENDERMINT_DEVD_CHUNK")
            env = 0
        if env > 0:
            return env
        if self._adv_chunk is None:
            try:
                self._adv_chunk = int(
                    self.ping().get("stream_chunk", 0)
                ) or DEFAULT_STREAM_CHUNK
            except Exception:  # noqa: BLE001 — daemon unreachable: the
                # stream attempt itself will surface the real error
                return DEFAULT_STREAM_CHUNK
        return self._adv_chunk

    def verify_stream(self, items, chunk: int | None = None) -> list[bool]:
        """Streamed verify_batch: same verdicts, pipelined transport."""
        return self.verify_stream_async(items, chunk=chunk)()

    def verify_stream_async(self, items, chunk: int | None = None):
        """Submit `items` as fixed-width chunk frames on one connection;
        a writer thread streams frames while the daemon verifies, and
        the returned zero-arg resolver collects per-chunk verdicts in
        order. A failed attempt on a pooled connection retries once on a
        fresh one (daemon restarts must not surface to the caller)."""
        items = list(items)
        if not items:
            return lambda: []
        width = max(1, chunk or self.stream_chunk())
        spans = [items[i: i + width] for i in range(0, len(items), width)]
        header = {
            "op": "verify_stream",
            "chunks": len(spans),
            "total": sum(len(s) for s in spans),
            "rid": _next_rid(),
        }
        return self._stream_resolver(
            spans, header, _pack_chunk, self._stream_stats,
            lambda conn, writer, werr: self._collect_stream(
                conn, writer, werr, len(spans)
            ),
        )

    def _stream_resolver(self, spans, header: dict, pack, stats, collect):
        """Open a chunked stream NOW and return the zero-arg resolver
        with the shared reconnect-once error triage (verify and hash
        planes): a DevdError is final; a writer error that is not an
        OSError is a deterministic client-side marshal failure (a retry
        would fail identically — surface the real cause); a transport
        failure on a POOLED connection retries once on a fresh one
        (daemon restarts must not surface to the caller)."""
        first = self._start_stream(spans, False, header, pack, stats)

        def resolve():
            conn, pooled, writer, werr = first
            try:
                return collect(conn, writer, werr)
            except DevdError:
                self._kill(conn)
                self._reap_writer(writer, stats, conn)
                raise
            except Exception as exc:
                self._kill(conn)
                self._reap_writer(writer, stats, conn)
                if werr and not isinstance(werr[0], OSError):
                    raise werr[0] from exc
                if not (pooled and isinstance(exc, (ConnectionError, EOFError))):
                    raise
                self._note_reconnect(stats, "midstream")
                conn2, _, writer2, werr2 = self._start_stream(
                    spans, True, header, pack, stats
                )
                try:
                    return collect(conn2, writer2, werr2)
                except Exception:
                    self._kill(conn2)
                    self._reap_writer(writer2, stats, conn2)
                    raise

        return resolve

    def _reap_writer(self, writer, stats: dict, conn) -> bool:
        """Join the writer thread under a bounded budget. An overrun is
        ABANDONMENT (satellite fix, round 8): the pre-r8 code silently
        walked away from a live writer wedged in sendall, leaving its
        thread and connection dangling with no trace in any counter.
        Now abandonment counts as a fault (`writer_abandoned`, surfaced
        through stream_* stats), and the connection is closed — which
        both unwedges the stuck sendall (it fails fast on the dead fd)
        and guarantees the socket can never re-enter the pool. Returns
        True when the writer had to be abandoned."""
        writer.join(timeout=WRITER_REAP_S)
        if not writer.is_alive():
            return False
        with self._mtx:
            stats["writer_abandoned"] += 1
        logger.warning(
            "stream writer abandoned after join timeout; closing its conn"
        )
        self._kill(conn)  # shutdown-then-close: unwedges a pinned sendall
        return True

    def _start_stream(self, spans, fresh: bool, header: dict, pack, stats):
        """Open one chunked stream (verify or hash plane): send the
        pickle header, then launch the writer thread that packs and
        streams chunk frames. `stats` is the client counter dict the
        writer notes its totals into (shared key shape)."""
        if fresh:
            conn, pooled = self._fresh(), False
        else:
            conn, pooled = self._acquire()
        try:
            conn.settimeout(self.claim_timeout)
            _send_frame(conn, header)
            # per-frame budget for the active stream: each chunk write
            # and each result read must make progress inside this window
            # (a stalled daemon surfaces as socket.timeout here instead
            # of sitting on the full flat io budget)
            conn.settimeout(self.stream_timeout)
        except Exception as exc:
            self._discard(conn)
            if not (pooled and isinstance(exc, (ConnectionError, EOFError))):
                raise
            self._note_reconnect(stats, "connect")
            return self._start_stream(spans, True, header, pack, stats)
        werr: list = []

        def write() -> None:
            # pack-as-you-send: marshaling chunk N+1 overlaps the
            # daemon's decode+verify of chunk N (and the resolver's
            # reads) — the client never builds the whole wire image
            try:
                sent_chunks = sent_bytes = sent_lanes = 0
                for span in spans:
                    payload = pack(span)
                    conn.sendall(struct.pack(">I", len(payload)) + payload)
                    sent_chunks += 1
                    sent_bytes += len(payload)
                    sent_lanes += len(span)
                with self._mtx:
                    stats["stream_batches"] += 1
                    stats["stream_chunks_out"] += sent_chunks
                    stats["stream_bytes_out"] += sent_bytes
                    stats["stream_lanes"] += sent_lanes
            except Exception as exc:  # noqa: BLE001 — surfaced by resolver
                werr.append(exc)
                # fail FAST on both sides: without this the daemon would
                # block reading the chunks that will never come and the
                # resolver would block on verdicts until io_timeout
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

        writer = threading.Thread(target=write, daemon=True,
                                  name="devd-stream-write")
        writer.start()
        return conn, pooled, writer, werr

    def _collect_stream(self, conn, writer, werr, n_chunks: int) -> list[bool]:
        import numpy as np

        chunk_hist = _latency_hists()[0].labels(op="verify")
        out: list[bool] = []
        for want in range(n_chunks):
            t0 = time.perf_counter()
            payload = _recv_raw_frame(conn)
            chunk_hist.observe(time.perf_counter() - t0)
            status, idx = struct.unpack_from("<BI", payload, 0)
            if status == STREAM_ERR:
                # the resolver's DevdError handler discards the conn and
                # reaps the writer (abandonment-counted) — no join here
                raise DevdError(
                    f"stream chunk {idx}: {payload[5:].decode(errors='replace')}"
                )
            if status not in (STREAM_OK, STREAM_ERR):
                if status == 0x80:  # a PICKLE frame: the daemon answered
                    # the verify_stream header with {"ok": False, ...} —
                    # it predates the streaming protocol. The marker
                    # below is what devd_backend latches single-shot on;
                    # any OTHER desync must NOT latch (it would silently
                    # disable the fast path over a transient bug).
                    raise DevdError("daemon too old for verify_stream")
                raise DevdError(
                    f"bad stream result frame (status {status}, chunk {want})"
                )
            if idx != want:
                raise DevdError(
                    f"stream result desync: got chunk {idx}, want {want}"
                )
            (n,) = struct.unpack_from("<I", payload, 5)
            if len(payload) != 9 + n:
                raise DevdError(f"result frame size mismatch for chunk {idx}")
            out.extend(
                np.frombuffer(payload, dtype=np.uint8, offset=9)
                .astype(bool).tolist()
            )
        abandoned = self._reap_writer(writer, self._stream_stats, conn)
        if werr:
            # results complete but the writer died — impossible unless
            # the daemon answered chunks it never received; be loud
            raise DevdError(f"stream writer failed: {werr[0]}")
        if not abandoned:
            conn.settimeout(self.io_timeout)  # back to pickle mode
            self._release(conn)
        return out

    # -- streamed hash transport --------------------------------------------

    def hash_batch(self, items, mode: str = "part", tree: bool = False):
        """Single-shot daemon hashing: one pickle frame each way. Digest
        list; with tree=True, (digests, postorder internal nodes)."""
        t0 = time.perf_counter()
        rep = self.request({
            "op": "hash", "mode": mode,
            "items": [bytes(b) for b in items], "tree": bool(tree),
        }, timeout=self.io_timeout)
        _observe_single("hash", t0, rep)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "hash failed"))
        with self._mtx:
            self._hash_stats["single_batches"] += 1
            self._hash_stats["single_lanes"] += len(rep["digests"])
        if tree:
            return rep["digests"], rep.get("nodes", [])
        return rep["digests"]

    def hash_stream(self, items, mode: str = "part", tree: bool = False,
                    chunk: int | None = None):
        """Streamed hash_batch: same digests, pipelined transport."""
        return self.hash_stream_async(items, mode=mode, tree=tree,
                                      chunk=chunk)()

    def hash_stream_async(self, items, mode: str = "part",
                          tree: bool = False, chunk: int | None = None):
        """Submit leaf payloads as chunked hash frames on one connection;
        the returned resolver collects per-chunk digest frames in order
        (plus the tree frame when tree=True → (digests, internal_nodes)).
        Reconnect-once semantics match verify_stream_async: a failed
        attempt on a pooled connection retries on a fresh one."""
        items = [bytes(b) for b in items]
        if not items:
            return (lambda: ([], [])) if tree else (lambda: [])
        width = max(1, chunk or self.stream_chunk())
        spans = [items[i: i + width] for i in range(0, len(items), width)]
        header = {
            "op": "hash_stream",
            "chunks": len(spans),
            "total": len(items),
            "mode": mode,
            "tree": bool(tree),
        }
        return self._stream_resolver(
            spans, header, _pack_hash_chunk, self._hash_stats,
            lambda conn, writer, werr: self._collect_hash_stream(
                conn, writer, werr, len(spans), tree
            ),
        )

    def _collect_hash_stream(self, conn, writer, werr, n_chunks: int,
                             want_tree: bool):
        chunk_hist = _latency_hists()[0].labels(op="hash")
        digests: list[bytes] = []
        for want in range(n_chunks):
            t0 = time.perf_counter()
            payload = _recv_raw_frame(conn)
            chunk_hist.observe(time.perf_counter() - t0)
            status, idx = struct.unpack_from("<BI", payload, 0)
            if status == STREAM_ERR:
                # resolver discards + reaps (see _collect_stream)
                raise DevdError(
                    f"hash stream chunk {idx}: "
                    f"{payload[5:].decode(errors='replace')}"
                )
            if status != STREAM_OK:
                if status == 0x80:  # pickle frame: pre-r7 daemon answered
                    # the header with {"ok": False, "error": "unknown op"}
                    raise DevdError("daemon too old for hash_stream")
                raise DevdError(
                    f"bad hash result frame (status {status}, chunk {want})"
                )
            if idx != want:
                raise DevdError(
                    f"hash stream desync: got chunk {idx}, want {want}"
                )
            (n,) = struct.unpack_from("<I", payload, 5)
            if len(payload) != 9 + 20 * n:
                raise DevdError(f"digest frame size mismatch for chunk {idx}")
            digests.extend(
                payload[9 + 20 * i: 29 + 20 * i] for i in range(n)
            )
        nodes: list[bytes] | None = None
        if want_tree:
            payload = _recv_raw_frame(conn)
            status, cnt = struct.unpack_from("<BI", payload, 0)
            if status == STREAM_ERR:
                raise DevdError(
                    f"hash stream tree: {payload[5:].decode(errors='replace')}"
                )
            if status != STREAM_TREE or len(payload) != 5 + 20 * cnt:
                raise DevdError(f"bad tree frame (status {status})")
            nodes = [payload[5 + 20 * i: 25 + 20 * i] for i in range(cnt)]
            with self._mtx:
                self._hash_stats["stream_trees"] += 1
        abandoned = self._reap_writer(writer, self._hash_stats, conn)
        if werr:
            raise DevdError(f"hash stream writer failed: {werr[0]}")
        if not abandoned:
            conn.settimeout(self.io_timeout)  # back to pickle mode
            self._release(conn)
        return (digests, nodes) if want_tree else digests

    def hash_stream_stats(self) -> dict:
        """Client-side hash-transport counters (ops/gateway.Hasher folds
        these in as flat stream_* gauges for the metrics RPC)."""
        with self._mtx:
            return dict(self._hash_stats)

    def stream_stats(self) -> dict:
        """Client-side streamed-transport counters (Verifier.stats()
        merges these under \"stream\" for the devd backend)."""
        with self._mtx:
            return dict(self._stream_stats)

    def status(self, timeout: float = 5.0) -> dict:
        """Ping plus the daemon's streamed-chunk observability counters."""
        rep = self.request({"op": "status"}, timeout=timeout)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "status failed"))
        return rep

    def stats(self) -> dict:
        rep = self.request({"op": "stats"})
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "stats failed"))
        return rep["stats"]

    def spans(self, since_ns: int = 0, last: int | None = None,
              timeout: float = 30.0) -> dict:
        """The daemon's ring of per-call records (devd_spans.FIELDS),
        oldest first, with the ring's `size` and total `count`."""
        rep = self.request({"op": "spans", "since_ns": int(since_ns),
                            "last": last}, timeout=timeout)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "spans failed"))
        return rep

    def profile_start(self, tdir: str, max_calls: int = 0,
                      timeout: float = 60.0) -> dict:
        """Start a jax.profiler trace inside the daemon; it stops by
        itself after `max_calls` verifier calls (0: only `profile_stop`
        stops it). A second start is refused with DevdError."""
        rep = self.request({"op": "profile", "action": "start", "dir": tdir,
                            "max_calls": int(max_calls)}, timeout=timeout)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "profile start failed"))
        return rep

    def profile_stop(self, timeout: float = 600.0) -> dict:
        """Stop the trace and wait until it is written out (about 3 s a
        traced verifier call on the chip); after it stopped by itself
        this returns that stop's answer."""
        rep = self.request({"op": "profile", "action": "stop"},
                           timeout=timeout)
        if not rep.get("ok"):
            raise DevdError(rep.get("error", "profile stop failed"))
        return rep

    def shutdown(self) -> None:
        self.request({"op": "shutdown"})

    def close(self) -> None:
        with self._mtx:
            pool, self._pool = self._pool, []
        for c in pool:
            self._discard(c)


# per-path probe cache: the sharded plane (ops/devd_shard) probes every
# endpoint independently, so one entry per socket path
_avail_cache: dict[str, tuple[float, dict | None]] = {}
_avail_mtx = threading.Lock()
_AVAIL_TTL = 15.0


def bust_avail_cache(path: str | None = None) -> None:
    """Force the next available() to ping fresh — failure paths must not
    trust a TTL-cached 'held' from a daemon that just died. No-arg busts
    every endpoint's entry; a path busts just that endpoint's."""
    with _avail_mtx:
        if path is None:
            _avail_cache.clear()
        else:
            _avail_cache.pop(path, None)


def available(timeout: float = 1.0, path: str | None = None) -> dict | None:
    """Liveness probe: the daemon's ping reply if a daemon is serving AND
    holds the device, else None. Never raises. Positive AND negative
    results are cached ~15s per socket path — the gateway consults this
    per batch on its kernel-selection default, and a ping (or a failed
    connect) per batch would dominate small-batch latency. `path` probes
    one sharded-plane endpoint; default is the primary socket."""
    path = path or sock_path()
    now = time.monotonic()
    with _avail_mtx:
        hit = _avail_cache.get(path)
        if hit is not None and now - hit[0] < _AVAIL_TTL:
            return hit[1]
    rep = None
    if os.path.exists(path):
        c = DevdClient(path, connect_timeout=timeout, io_timeout=timeout)
        try:
            r = c.ping(timeout=timeout)
            rep = r if r.get("held") else None
        except TimeoutError:
            # somebody listens and did not answer in time: a daemon that
            # is compiling, or a host with more processes than cores. That
            # is no verdict on it, so it is not cached as one: the next
            # caller asks again (gateway.resolve_platform waits it out)
            return None
        except Exception:  # noqa: BLE001 — nobody there
            rep = None
        finally:
            c.close()
    with _avail_mtx:
        _avail_cache[path] = (now, rep)
    return rep


def main() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    serve()


if __name__ == "__main__":
    main()
