#!/usr/bin/env python3
"""The benchmark's launcher for the device daemon.

Runs `tendermint_tpu.devd`'s own entry point in THIS process (the one
process that may load libtpu) and adds, from the benchmark's side only:

- a `jax.monitoring` listener that stamps every program the process
  compiles or loads from the persistent cache (one
  `/jax/core/compile/backend_compile_duration` event each) with the wall
  clock, so the harness can count compilations inside the window;
- spans around the daemon's verifier calls (dispatch -> verdicts read),
  on the wall clock, so idle gaps of the device can be attributed;
- `jax.profiler` start/stop on request, with a marker annotation that
  ties the trace's clock to the wall clock; a trace stops by itself at its
  12th verifier call, and at the harness's stop only once it holds one;
- the device's `memory_stats()` peak on request.

Requests are files: the harness writes `<ctl>/req-<n>.json`
({"op": ...}), the launcher answers `<ctl>/ack-<n>.json`. Files because
the daemon's socket protocol belongs to the program and is not touched.

`--control accept-all` replaces the verdicts of the daemon's verifier
with all-True. It exists for the benchmark's control runs and tests
("a verifier that skips verification must come out as not correct") and
is never set by `run.py` in a measured run. `--control half-batch`
verifies the first half of every batch and answers True for the rest.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
POLL_S = 0.02
# every execution of the verify kernel is some 22,000 device events, about
# 2.6 MB of trace and 3 s of writing it out (measured, PERF.md: 20 calls,
# 58-75 s): a trace that has seen this many verifier calls stops by itself
MAX_TRACED_CALLS = 12
# at the harness's stop, a trace that holds no verifier call yet stays open
# until the first one lands, this long at most: a committee spends a
# second of each height in `timeout_commit`, with gate checks alone (some
# 15 a second) reaching the daemon, and its nodes run on after the close
FIRST_CALL_WAIT_S = 5.0


class JaxProfiler:
    """`jax.profiler`, with a marker annotation that ties the trace's
    clock to the wall clock at each end."""

    @staticmethod
    def mark(wall_ns: int) -> None:
        import jax

        with jax.profiler.TraceAnnotation(f"bench_mark:{wall_ns}"):
            time.sleep(0.001)

    @staticmethod
    def start(tdir: str) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)

    @staticmethod
    def stop() -> None:
        import jax

        jax.profiler.stop_trace()


class Recorder:
    """What the launcher observes, on the wall clock (ns). `clock` and
    `sleep` pace the wait for a first traced call; tests give their own,
    and a profiler that records nothing."""

    def __init__(self, profiler=JaxProfiler, clock=time.monotonic,
                 sleep=time.sleep) -> None:
        self.lock = threading.Lock()
        self.compiles: list[tuple[int, float]] = []   # (end_wall_ns, seconds)
        self.spans: list[tuple[int, int, int]] = []   # (start_ns, end_ns, lanes)
        self.tracing_since: int | None = None         # len(spans) at the start
        self.trace_result: dict | None = None
        self.trace_lock = threading.Lock()
        self.profiler, self.clock, self.sleep = profiler, clock, sleep

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self.lock:
                self.compiles.append((time.time_ns(), float(duration)))

    def add_span(self, t0: int, t1: int, lanes: int) -> None:
        with self.lock:
            self.spans.append((t0, t1, lanes))
            over = self.tracing_since is not None and \
                len(self.spans) - self.tracing_since >= MAX_TRACED_CALLS
        if over:
            # on a thread of its own: the writing takes the better part of
            # a minute, and the call that tripped the bound must not wait
            # for it (nor must the nodes behind it)
            threading.Thread(target=self.stop_trace, name="bench-stop-trace").start()

    def start_trace(self, tdir: str) -> dict:
        self.profiler.start(tdir)
        wall = time.time_ns()
        self.profiler.mark(wall)
        with self.lock:
            self.tracing_since = len(self.spans)
            self.trace_result = None
        return {"ok": True, "start_wall_ns": wall}

    def traced_calls(self) -> int | None:
        """Verifier calls since the trace started; None where none runs."""
        with self.lock:
            if self.tracing_since is None:
                return None
            return len(self.spans) - self.tracing_since

    def stop_trace(self, wait_s: float = 0.0) -> dict:
        """Stop once; a second call (the harness's, after the trace
        stopped by itself) gets the first one's answer. A trace that holds
        no verifier call yet stays open until the first one lands, `wait_s`
        at most; one that still holds none stops all the same, and says so
        (`traced_calls` 0)."""
        t0 = self.clock()
        while self.traced_calls() == 0 and self.clock() - t0 < wait_s:
            self.sleep(POLL_S)
        waited = self.clock() - t0
        with self.trace_lock:
            with self.lock:
                if self.tracing_since is None:
                    return self.trace_result or {"ok": False,
                                                 "error": "no trace was started"}
                calls = len(self.spans) - self.tracing_since
                self.tracing_since = None
            wall = time.time_ns()
            self.profiler.mark(wall)
            self.profiler.stop()
            self.trace_result = {"ok": True, "stop_wall_ns": wall,
                                 "written_wall_ns": time.time_ns(),
                                 "traced_calls": calls,
                                 "waited_for_call_s": waited}
            return self.trace_result

    def snapshot(self, since_ns: int = 0) -> dict:
        with self.lock:
            return {
                "compiles": [c for c in self.compiles if c[0] >= since_ns],
                "spans": [s for s in self.spans if s[1] >= since_ns],
            }


def wrap_verifier(verifier, rec: Recorder, control: str):
    """Span (and, for a control run, falsify) the daemon verifier's two
    entry points. The wrapped object is the program's own Verifier."""
    inner_async = verifier.verify_batch_async

    def verify_batch_async(items):
        items = list(items)
        n = len(items)
        t0 = time.time_ns()
        if control == "half-batch":
            resolve_inner = inner_async(items[: n // 2])
        else:
            resolve_inner = inner_async(items)

        def resolve():
            out = resolve_inner()
            rec.add_span(t0, time.time_ns(), n)
            if control == "accept-all":
                return [True] * n
            if control == "half-batch":
                return [bool(b) for b in out] + [True] * (n - n // 2)
            return out

        return resolve

    def verify_batch(items):
        return verify_batch_async(items)()

    verifier.verify_batch_async = verify_batch_async
    verifier.verify_batch = verify_batch
    return verifier


def install_verifier_wrap(devd, rec: Recorder, control: str) -> None:
    """The daemon builds its Verifier inside `_claim`; wrap it when the
    claim hands it over (the state object's `verifier` attribute)."""
    state_cls = devd._DaemonState
    slot = "_bench_verifier"

    def get(self):
        return getattr(self, slot, None)

    def set_(self, v):
        if v is not None and hasattr(v, "verify_batch_async") and \
                not getattr(v, "_bench_wrapped", False):
            v = wrap_verifier(v, rec, control)
            v._bench_wrapped = True
        setattr(self, slot, v)

    state_cls.verifier = property(get, set_)


def control_loop(ctl: str, rec: Recorder, stop: threading.Event) -> None:
    seen: set[str] = set()
    while not stop.is_set():
        try:
            names = sorted(n for n in os.listdir(ctl)
                           if n.startswith("req-") and n.endswith(".json"))
        except OSError:
            names = []
        for name in names:
            if name in seen:
                continue
            seen.add(name)
            try:
                with open(os.path.join(ctl, name)) as f:
                    req = json.load(f)
            except (OSError, ValueError):
                seen.discard(name)   # still being written
                continue
            ack = handle(req, rec)
            tmp = os.path.join(ctl, "." + name)
            with open(tmp, "w") as f:
                json.dump(ack, f)
            os.replace(tmp, os.path.join(ctl, "ack-" + name[4:]))
        time.sleep(POLL_S)


def handle(req: dict, rec: Recorder) -> dict:
    op = req.get("op")
    try:
        if op == "snapshot":
            out = rec.snapshot(int(req.get("since_ns", 0)))
            out["wall_ns"] = time.time_ns()
            return {"ok": True, **out}
        if op == "device":
            import jax

            devs = jax.devices()
            peak = 0
            for d in devs:
                stats = d.memory_stats() or {}
                peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
            return {"ok": True, "platform": devs[0].platform,
                    "kind": devs[0].device_kind, "count": len(devs),
                    "memory_peak_bytes": peak}
        if op == "start_trace":
            return rec.start_trace(req["dir"])
        if op == "stop_trace":
            return rec.stop_trace(FIRST_CALL_WAIT_S)
        return {"ok": False, "error": f"unknown op {op!r}"}
    except Exception as exc:  # noqa: BLE001 — reported to the harness
        return {"ok": False, "error": f"{type(exc).__name__}: {exc}"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True, help="the checkout's root")
    ap.add_argument("--ctl", required=True, help="request/ack directory")
    ap.add_argument("--control", default="",
                    choices=("", "accept-all", "half-batch"))
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    os.makedirs(args.ctl, exist_ok=True)

    import logging

    import jax.monitoring

    from tendermint_tpu import devd

    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_duration)
    install_verifier_wrap(devd, rec, args.control)
    stop = threading.Event()
    threading.Thread(target=control_loop, args=(args.ctl, rec, stop),
                     daemon=True, name="bench-ctl").start()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    try:
        devd.serve()
    finally:
        stop.set()


if __name__ == "__main__":
    main()
