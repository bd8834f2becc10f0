"""Per-call records of the device daemon, taken from inside it.

Every `verify`, every chunk of a `verify_stream` and every `agg` the
daemon serves leaves ONE record: six instants on `time.time_ns()` that
bound five phases, named the same in the record, in the profiler's trace
and in the benchmark's metrics:

    decode       t_recv0 (the frame's length header in hand; NOT the idle
                 wait for it on a pooled connection) -> items in hand:
                 the body's read, the unpickle / `_unpack_chunk`
    marshal      -> arrays ready: `_split_by_key_type`, `_bump_seen`,
                 `prepare_batch8`, `CombPool.ensure`
    dispatch     -> the jit call has returned: `jnp.asarray` of the
                 arrays, `_verify_jit(...)`
    device_wait  -> verdicts on the host: the resolver's
                 `np.asarray(ok_dev)` (device time, waiting behind
                 another call's program, the read-back)
    reply        -> the reply frame sent

The daemon opens a record at `t_recv0` and leaves it in a thread-local;
the code it calls ends a phase with `mark(<phase>)`, which is one
attribute test when no record is open: every call in a node process and
in the tests. Modelled on `consensus/trace.TraceRecorder.mark`: one
writer at a time (a stream's chunk is dispatched on the reader thread and
resolved on the sender thread, and the record travels with it), the
phases PARTITION `t_replied - t_recv0` exactly, the first mark of a phase
wins, and a phase nobody marked has length zero at the instant the next
marked one ends (so a kernel without marks shows its whole call under
the first phase it left open; `ops/ed25519_comb` and `ops/ed25519_f32`
mark all three of theirs). Where one call dispatches twice (comb lanes
and first-sight ladder lanes), the second dispatch's host work lies in
`device_wait`.

Each phase is also a `jax.profiler.TraceAnnotation("devd.<phase>",
seq=..., lanes=...)`: whatever trace is active in the process then holds
the daemon's host spans on the timeline of the device's `XLA Modules`
events, with no offset to estimate. `Profile` starts and stops such a
trace from inside (the daemon's `profile` op).

Completed records land in a ring of RING_SIZE (a constant, not a knob),
served by the daemon's `spans` op and written out as JSON lines when
`serve()` returns.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time

PHASES = ("decode", "marshal", "dispatch", "device_wait", "reply")
_INDEX = {p: i for i, p in enumerate(PHASES)}
_NAMES = tuple("devd." + p for p in PHASES)
# one record, as the ring, the `spans` op and the dump's lines hold it
FIELDS = (
    "seq", "conn", "op", "lanes", "width",
    "t_recv0", "t_decoded", "t_marshalled", "t_dispatched", "t_verdicts",
    "t_replied", "in_flight_at_recv", "rid",
    # the program the request rode (devd's merge of waiting `verify`
    # requests): the leading record's seq, how many requests and how many
    # connections it carried, and its lanes. A call that ran alone reads
    # its own seq, 1, 1 and its own lanes.
    "program", "merged", "merged_conns", "program_lanes",
    # what the comb kernel's pool did for the call (`note`; on the leading
    # record of a merged program): `ran` is all_hit (every comb lane's key
    # resident), with_build (a table was built), with_ladder (lanes on the
    # first-sight ladder beside comb lanes) or ladder_only; the keys built
    # and the slots evicted for them; the lanes the ladder took; the
    # host's nanoseconds inside the build and the pool-update calls (both
    # lie inside the record's `marshal`).
    "ran", "keys_built", "slots_evicted", "lanes_ladder", "build_ns",
    "update_ns",
    # who asked and why, parsed from the client's rid at decode
    # (`<node>-<why>-<counter>`, docs/device-daemon.md); "" for a rid of another form
    "node", "why",
)
NOTED = ("keys_built", "slots_evicted", "lanes_ladder", "build_ns",
         "update_ns")
RING_SIZE = 65536
CLOCK_MARK = "devd.clock:"

_tls = threading.local()
_TraceAnnotation = None


def mark(phase: str, width: int = 0) -> None:
    """End `phase` of the call this thread is serving, now. `width` is
    the padded bucket the kernel runs the call at."""
    rec = getattr(_tls, "rec", None)
    if rec is not None:
        rec.mark(phase, width)


def note(**kw) -> None:
    """Add to the counts (NOTED) of the call this thread is serving."""
    rec = getattr(_tls, "rec", None)
    if rec is not None:
        for k, v in kw.items():
            setattr(rec, k, getattr(rec, k) + v)


def _annotation(name: str, **kw):
    """An entered TraceAnnotation, once this process has imported jax
    (the daemon that claimed a device has; the sim daemon has not and
    stays off it). When no trace is active it costs its construction."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        jax = sys.modules.get("jax")
        prof = getattr(jax, "profiler", None)
        if prof is None:
            return None
        _TraceAnnotation = prof.TraceAnnotation
    ann = _TraceAnnotation(name, **kw)
    ann.__enter__()
    return ann


class CallRecord:
    """One request's instants. `t[0]` is t_recv0, `t[i + 1]` the end of
    PHASES[i]; `_cur` is the phase now running."""

    __slots__ = ("seq", "conn", "op", "lanes", "width", "rid", "node", "why",
                 "in_flight", "t", "_cur", "_ann", "_counted", "_closed",
                 "program", "merged", "merged_conns", "program_lanes",
                 *NOTED)

    def __init__(self, seq: int, conn: int, in_flight: int):
        self.seq = seq
        self.conn = conn
        self.op = ""
        self.lanes = 0
        self.width = 0
        self.rid = self.node = self.why = ""
        self.in_flight = in_flight
        self.t = [time.time_ns(), 0, 0, 0, 0, 0]
        self._cur = 0
        self._counted = False
        self._closed = False
        self.program = seq
        self.merged = 1
        self.merged_conns = 1
        self.program_lanes = 0
        self.keys_built = self.slots_evicted = self.lanes_ladder = 0
        self.build_ns = self.update_ns = 0
        self._ann = _annotation(_NAMES[0], seq=seq)

    def mark(self, phase: str, width: int = 0) -> None:
        k = _INDEX[phase]
        if k < self._cur:
            return  # the first mark of a phase wins
        now = time.time_ns()
        if width:
            self.width = width
        ann = self._ann
        if ann is not None:
            ann.__exit__(None, None, None)
        # the running phase gets the time; phases skipped over are empty
        for j in range(self._cur, k + 1):
            self.t[j + 1] = now
            if ann is not None and j > self._cur:
                _annotation(_NAMES[j], seq=self.seq, lanes=self.lanes) \
                    .__exit__(None, None, None)
        self._cur = k + 1
        self._ann = _annotation(_NAMES[k + 1], seq=self.seq,
                                lanes=self.lanes) \
            if ann is not None and k + 1 < len(PHASES) else None

    def ride(self, lead: "CallRecord", requests: int, conns: int,
             lanes: int) -> None:
        """This request rode `lead`'s program with `requests` - 1 others:
        note the program on the record and, where it is not the leading
        one, take the program's three phases from the leader (its wait in
        the daemon's queue then lies in `marshal`). Only the leader's
        phases are annotations: one program, one set of spans."""
        self.program, self.merged = lead.seq, requests
        self.merged_conns, self.program_lanes = conns, lanes
        if lead is self:
            return
        ann = self._ann
        if ann is not None:
            ann.__exit__(None, None, None)
        # a request decoded while the leader marshalled joined it late:
        # its phases cannot end before they began
        for j in range(max(self._cur, 1), 4):
            self.t[j + 1] = max(lead.t[j + 1], self.t[j])
        self.width = lead.width
        self._cur = 4
        self._ann = _annotation(_NAMES[4], seq=self.seq, lanes=self.lanes) \
            if ann is not None else None

    def service_ns(self) -> int:
        """t_recv0 until now: what the reply carries as `svc_ns`."""
        return time.time_ns() - self.t[0]

    def ran(self) -> str:
        if self.keys_built:
            return "with_build"
        if not self.lanes_ladder:
            return "all_hit"
        lanes = self.program_lanes or self.lanes
        return "ladder_only" if self.lanes_ladder >= lanes else "with_ladder"

    def row(self) -> tuple:
        return (self.seq, self.conn, self.op, self.lanes, self.width,
                *self.t, self.in_flight, self.rid, self.program,
                self.merged, self.merged_conns,
                self.program_lanes or self.lanes, self.ran(),
                self.keys_built, self.slots_evicted, self.lanes_ladder,
                self.build_ns, self.update_ns, self.node, self.why)


def parse_rid(rid: str) -> tuple[str, str]:
    """(node, why) of a client's `<node>-<why>-<counter>`; ("", "") for a
    rid of any other form (an older client's `<pid>-<counter>`)."""
    parts = rid.rsplit("-", 2)
    if len(parts) == 3 and parts[0] and parts[1] and parts[2].isdigit():
        return parts[0], parts[1]
    return "", ""


def attach(rec: CallRecord | None) -> None:
    """Make `rec` this thread's (None: this thread serves no call). A
    stream's chunk changes threads between dispatch and verdicts."""
    _tls.rec = rec


class SpanRing:
    """The daemon's ring of completed records, and the count of requests
    between t_recv0 and t_replied (the queue a call meets)."""

    def __init__(self, size: int = RING_SIZE):
        self.size = size
        self._rows: list = [None] * size
        self._lock = threading.Lock()
        self._seq = itertools.count(1)
        self.count = 0   # records ever written; past `size` the ring wrapped
        self.open = 0
        self.on_record = None  # Profile.note_call while a profile runs

    def begin(self, conn: int) -> CallRecord:
        """Open a record at t_recv0 and make it this thread's."""
        rec = CallRecord(next(self._seq), conn, self.open)
        _tls.rec = rec
        return rec

    def decoded(self, rec: CallRecord, op: str, lanes: int,
                rid: str = "") -> None:
        """The request is one the ring keeps: name it, end `decode`."""
        rec.op, rec.lanes, rec.rid = op, lanes, rid
        rec.node, rec.why = parse_rid(rid)
        with self._lock:
            self.open += 1
        rec._counted = True
        rec.mark("decode")

    def _close(self, rec: CallRecord, row: tuple | None) -> None:
        rec._closed = True
        if getattr(_tls, "rec", None) is rec:
            _tls.rec = None
        with self._lock:
            if rec._counted:
                self.open -= 1
                rec._counted = False
            if row is not None:
                self._rows[self.count % self.size] = row
                self.count += 1
        on_record = self.on_record
        if row is not None and on_record is not None:
            on_record()

    def finish(self, rec: CallRecord) -> None:
        """The reply is sent: end `reply`, write the record."""
        rec.mark("reply")
        self._close(rec, rec.row())

    def drop(self, rec: CallRecord | None) -> None:
        """A request the ring does not keep (ping, status, hash) or one
        that failed (the error is in the log and in the reply)."""
        if rec is None or rec._closed:
            return
        if rec._ann is not None:
            rec._ann.__exit__(None, None, None)
            rec._ann = None
        self._close(rec, None)

    def rows(self, since_ns: int = 0, last: int | None = None) -> list:
        """Oldest first; `since_ns` keeps records with t_recv0 at or
        after it, `last` the newest that many of those."""
        with self._lock:  # a C-level copy: writers wait microseconds
            rows, count = self._rows[:], self.count
        head = count % self.size
        out = rows[:count] if count <= self.size else rows[head:] + rows[:head]
        if since_ns:
            out = [r for r in out if r[5] >= since_ns]
        if last is not None:
            out = out[-max(0, int(last)):] if int(last) > 0 else []
        return out

    def stats(self) -> dict:
        return {"size": self.size, "count": self.count, "open": self.open}

    def dump(self, path: str, **header) -> str | None:
        """The ring as JSON lines: one header line (the fields, the
        ring's size and total count, whatever the caller adds), then one
        list per record. Never raises: the daemon is stopping."""
        rows = self.rows()
        head = {"fields": list(FIELDS), "phases": list(PHASES),
                "clock": "time.time_ns", "ring_size": self.size,
                "count": self.count, "records": len(rows),
                "written_at": time.time(), **header}
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(json.dumps(head) + "\n")
                for r in rows:
                    f.write(json.dumps(r, separators=(",", ":")) + "\n")
            os.replace(tmp, path)
            return path
        except OSError:
            return None


def dump_path(sock: str, suffix: str = ".spans.jsonl") -> str:
    """The socket's path with `.sock` replaced by `.spans.jsonl` (or by
    another dump's suffix)."""
    stem = sock[:-len(".sock")] if sock.endswith(".sock") else sock
    return stem + suffix


class Profile:
    """`jax.profiler` from inside the daemon: what a launcher did from
    outside. One trace at a time; it stops by itself after `max_calls`
    records, on a thread of its own (writing a trace out takes about 3 s
    a traced verifier call on the chip: a call must never wait for it)."""

    def __init__(self, ring: SpanRing):
        self._ring = ring
        self._lock = threading.Lock()
        self._stop_lock = threading.Lock()
        self._active: dict | None = None
        self._result: dict | None = None

    @staticmethod
    def _clock_mark() -> int:
        wall = time.time_ns()
        ann = _annotation(CLOCK_MARK + str(wall))
        time.sleep(0.001)
        ann.__exit__(None, None, None)
        return wall

    def start(self, tdir: str, max_calls: int) -> dict:
        jax = sys.modules.get("jax")
        if jax is None:
            return {"ok": False, "error": "this daemon runs no jax"}
        with self._lock:
            if self._active is not None:
                return {"ok": False, "error": "a profile is already running"}
            self._active = {"dir": tdir, "max_calls": int(max_calls),
                            "calls": 0}
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        except Exception as exc:  # noqa: BLE001 — an error reply, never a raise
            with self._lock:
                self._active = None
            return {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
        wall = self._clock_mark()
        with self._lock:
            self._result = None
            self._active["start_wall_ns"] = wall
            self._ring.on_record = self.note_call
        return {"ok": True, "start_wall_ns": wall, "dir": tdir}

    def note_call(self) -> None:
        with self._lock:
            act = self._active
            if act is None:
                return
            act["calls"] += 1
            over = 0 < act["max_calls"] == act["calls"]
        if over:
            threading.Thread(target=self.stop, daemon=True,
                             name="devd-profile-stop").start()

    def stop(self) -> dict:
        """Stop once; a second call (the operator's, after the trace
        stopped by itself) gets the first one's answer."""
        jax = sys.modules.get("jax")
        with self._stop_lock:
            with self._lock:
                act = self._active
                if act is None:
                    return self._result or {
                        "ok": False, "error": "no profile was started"}
                self._active = None
                self._ring.on_record = None
            try:
                wall = self._clock_mark()
                jax.profiler.stop_trace()
            except Exception as exc:  # noqa: BLE001
                res = {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
            else:
                done = time.time_ns()
                res = {"ok": True, "dir": act["dir"],
                       "start_wall_ns": act.get("start_wall_ns"),
                       "stop_wall_ns": wall, "written_wall_ns": done,
                       "stop_trace_s": (done - wall) / 1e9,
                       "traced_calls": act["calls"]}
            with self._lock:
                self._result = res
            return res

    def active(self) -> bool:
        with self._lock:
            return self._active is not None
