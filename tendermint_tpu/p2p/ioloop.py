"""One I/O loop a node: every peer connection's reads and writes on one
thread, `p2p.io`, over the standard `selectors` module.

Upstream runs a `sendRoutine` and a `recvRoutine` a connection
(p2p/connection.go). At 15 peers that is 30 threads in one interpreter,
and each received frame woke its connection's reader, which then had to
take the interpreter lock back from some 60 other threads; each send woke
the peer's sender. Here a connection is a registration (`MConnection`
does the protocol, this module the waiting): on a readable socket the
loop reads what it holds, the connection opens every whole frame in it
and hands each complete message to its `on_receive`; a send queues on its
channel as before and marks the connection, and on the loop the
connection seals what is queued and writes it without blocking, keeping
an unsent tail until the socket takes it. Pings, pong time-outs and the
rate limits are instants on the loop's clock. The wire is unchanged.

The loop never blocks, so neither may what it calls, receive callbacks
included. Two hand-overs that used to wait on a connection's own reader
would wait on the loop here:
- a consumer queue that is full: `hold_reads` parks the reads of the
  connection being read (not the loop) and retries the hand-over;
- `MConnection.send` from a callback, which used to wait for room that
  only the loop frees: on the loop it queues past the channel's cap.

The loop's thread starts with its first connection. A switch owns one
loop (`Switch.io`); a connection no switch owns (a tool, a test) joins
`shared_loop()`.
"""

from __future__ import annotations

import logging
import math
import selectors
import socket
import threading
import time

# how often a connection whose hand-over is full tries it again
HOLD_RETRY_S = 0.002

_here = threading.local()  # .conn: the connection the loop is reading now

logger = logging.getLogger("p2p.io")


def _selector() -> selectors.BaseSelector:
    # poll keeps no state in the kernel: a descriptor closed while it is
    # registered reads as an error on it, never as another socket's events
    if hasattr(selectors, "PollSelector"):
        return selectors.PollSelector()
    return selectors.DefaultSelector()


class IOLoop:
    """The loop. Connections speak to it through `add`, `remove`, `mark`
    and, on the loop only, `set_events`; it calls back a connection's
    `_io_read`, `_io_write`, `_io_send(now)`, `_io_timer(now)` and
    `_fatal(exc)` and reads its `_io_sock`, `_io_due` (the instant of
    its next timer, `math.inf` for none) and `_io_dead`."""

    def __init__(self, name: str = "p2p.io"):
        self.name = name
        self._mtx = threading.Lock()
        self._calls: list = []       # run on the loop, in order
        self._dirty: set = set()     # connections with something to send
        self._signalled = False      # a wake byte is on its way
        self._conns: set = set()
        self._events: dict = {}      # connection -> its registered events
        self._thread: threading.Thread | None = None
        self._ident: int | None = None
        self._stopping = False
        self._done = False
        self._sel = None
        self._wake_r = self._wake_w = None
        # the stop dumps' p2p_io_* counters (docs/observability.md); one
        # writer, the loop's thread
        self.wakes = 0
        self.frames_in = 0
        self.frames_out = 0

    # -- any thread -----------------------------------------------------------

    def on_loop(self) -> bool:
        return threading.get_ident() == self._ident

    def add(self, conn) -> None:
        """Serve `conn` from now on (its handshake is done)."""
        if not self._post(lambda: self._attach(conn), start=True):
            raise ConnectionError("p2p I/O loop stopped")

    def remove(self, conn) -> None:
        """Stop serving `conn` and close its stream, on the loop: a socket
        is unregistered before it is closed."""
        conn._io_dead = True
        if self.on_loop():
            self._drop(conn)
        elif not self._post(lambda: self._drop(conn)):
            conn._io_close_stream()

    def mark(self, conn) -> None:
        """`conn` has something to send."""
        with self._mtx:
            if conn in self._dirty:
                return
            self._dirty.add(conn)
            if (self._signalled or self._thread is None
                    or threading.get_ident() == self._ident):
                return
            self._signalled = True
        self._signal()

    def stop(self, timeout: float = 2.0) -> None:
        """Drop every connection, end the thread."""
        with self._mtx:
            if self._stopping:
                return
            self._stopping = True
            thread = self._thread
        if thread is None:
            return
        self._signal()
        if not self.on_loop():
            thread.join(timeout)

    def stats(self) -> dict:
        return {"p2p_io_wakes": self.wakes,
                "p2p_io_frames_in": self.frames_in,
                "p2p_io_frames_out": self.frames_out}

    def _post(self, fn, start: bool = False) -> bool:
        """Run `fn` on the loop (starting it if `start`); False when the
        loop has ended, or never began, and will run nothing more."""
        with self._mtx:
            if self._thread is None:
                if not start or self._stopping:
                    return False
                self._begin()
            elif self._done:
                return False
            self._calls.append(fn)
            signal = not self._signalled and threading.get_ident() != self._ident
            self._signalled = self._signalled or signal
        if signal:
            self._signal()
        return True

    def _begin(self) -> None:  # under _mtx
        self._sel = _selector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, None)
        self._thread = threading.Thread(target=self._run, name=self.name,
                                        daemon=True)
        self._thread.start()

    def _signal(self) -> None:
        try:
            self._wake_w.send(b"\0")
        except OSError:
            pass  # full: the loop is awake already; closed: it has ended

    # -- the loop's thread ------------------------------------------------------

    def set_events(self, conn, events: int) -> None:
        """Register `conn`'s socket for `events` (0: for nothing)."""
        was = self._events.get(conn, 0)
        if events == was:
            return
        sock = conn._io_sock
        if was == 0:
            self._sel.register(sock, events, conn)
        elif events == 0:
            self._sel.unregister(sock)
        else:
            self._sel.modify(sock, events, conn)
        self._events[conn] = events

    def _attach(self, conn) -> None:
        if conn._io_dead:
            conn._io_close_stream()
            return
        self._conns.add(conn)
        self._guard(conn, conn._io_attached, time.monotonic())

    def _detach(self, conn) -> None:
        if self._events.pop(conn, 0):
            try:
                self._sel.unregister(conn._io_sock)
            except (KeyError, ValueError, OSError):
                pass
        self._conns.discard(conn)

    def _drop(self, conn) -> None:
        conn._io_dead = True
        self._detach(conn)
        conn._io_close_stream()

    def _guard(self, conn, fn, *args) -> None:
        """Call `fn` for `conn`; an error ends `conn`, never the loop."""
        _here.conn = conn
        try:
            fn(*args)
        except Exception as exc:  # noqa: BLE001 — any error is the connection's
            self._detach(conn)
            conn._io_dead = True
            try:
                conn._fatal(exc)
            except Exception:  # noqa: BLE001 — the loop outlives a callback
                logger.exception("p2p.io: error callback raised")
        finally:
            _here.conn = None

    def _timeout(self) -> float | None:
        due = min((c._io_due for c in self._conns), default=math.inf)
        if due == math.inf:
            return None
        return max(0.0, due - time.monotonic())

    def _run(self) -> None:
        self._ident = threading.get_ident()
        sel = self._sel
        while True:
            with self._mtx:
                busy = bool(self._calls or self._dirty) or self._stopping
            events = sel.select(0 if busy else self._timeout())
            self.wakes += 1
            for key, mask in events:
                conn = key.data
                if conn is None:
                    self._drain_wake()
                    continue
                if conn._io_dead:
                    continue
                if mask & selectors.EVENT_READ:
                    self._guard(conn, conn._io_read)
                if mask & selectors.EVENT_WRITE and not conn._io_dead:
                    self._guard(conn, conn._io_write)
            with self._mtx:
                calls, self._calls = self._calls, []
                dirty, self._dirty = self._dirty, set()
                self._signalled = False
                stopping = self._stopping
            for fn in calls:
                try:
                    fn()
                except Exception:  # noqa: BLE001
                    logger.exception("p2p.io: call raised")
            now = time.monotonic()
            for conn in dirty:
                if not conn._io_dead and conn in self._conns:
                    self._guard(conn, conn._io_send, now)
            for conn in [c for c in self._conns if c._io_due <= now]:
                if not conn._io_dead:
                    self._guard(conn, conn._io_timer, now)
            if stopping:
                break
        self._end()

    def _drain_wake(self) -> None:
        try:
            while self._wake_r.recv(4096):
                pass
        except OSError:
            pass

    def _end(self) -> None:
        with self._mtx:
            self._done = True
            calls, self._calls = self._calls, []
        for fn in calls:
            try:
                fn()
            except Exception:  # noqa: BLE001
                logger.exception("p2p.io: call raised")
        for conn in list(self._conns):
            self._drop(conn)
        self._sel.close()
        self._wake_r.close()
        self._wake_w.close()


def hold_reads(retry, give_up, timeout: float) -> None:
    """For a receive callback whose hand-over is full: park the reads of
    the connection being read (its later messages wait, in order; other
    connections go on), call `retry()` every HOLD_RETRY_S until it
    returns True, or `give_up()` once `timeout` has passed. Called off
    the loop (a callback run by hand) there is no connection to hold
    back: `give_up()` at once."""
    conn = getattr(_here, "conn", None)
    if conn is None:
        give_up()
        return
    conn._io_hold(retry, give_up, time.monotonic() + timeout)


_shared: IOLoop | None = None
_shared_mtx = threading.Lock()


def shared_loop() -> IOLoop:
    """The loop of the connections no switch owns."""
    global _shared
    with _shared_mtx:
        if _shared is None:
            _shared = IOLoop("p2p.io.shared")
        return _shared
