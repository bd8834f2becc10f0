"""The launcher's trace bounds: the verifier call that trips the count
returns at once (the trace is written out on a thread of its own), and the
harness's later stop gets that first stop's answer; at the harness's stop
a trace that holds no call yet waits for its first, within a bound. A
profiler that records nothing and a clock of the test's own stand in for
`jax.profiler` and the time, so this process never imports JAX."""

import threading
import time

import pytest

from harness import devd_launcher


class FakeProfiler:
    def __init__(self, stop_s=0.0):
        self.events, self.stop_s = [], stop_s

    def mark(self, wall_ns):
        self.events.append("mark")

    def start(self, tdir):
        self.events.append("start")

    def stop(self):
        self.events.append("stop")
        time.sleep(self.stop_s)          # stands for a minute of writing


class FakeClock:
    """Seconds that pass only when the recorder sleeps; `at` lands a
    verifier call once the clock has passed its instant."""

    def __init__(self, rec_box, calls_at=()):
        self.t, self.box, self.due = 0.0, rec_box, sorted(calls_at)

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt
        while self.due and self.due[0] <= self.t:
            self.due.pop(0)
            self.box[0].add_span(time.time_ns(), time.time_ns() + 1000, 1)


def recorder(calls_at=(), stop_s=0.0):
    box = [None]
    clock = FakeClock(box, calls_at)
    rec = devd_launcher.Recorder(profiler=FakeProfiler(stop_s), clock=clock,
                                 sleep=clock.sleep)
    box[0] = rec
    return rec, clock


def calls(rec, n):
    for _ in range(n):
        rec.add_span(time.time_ns(), time.time_ns() + 1000, 1)


def test_trace_stops_by_itself_without_holding_the_call(tmp_path):
    rec, _clock = recorder(stop_s=0.5)
    assert rec.start_trace(str(tmp_path))["ok"]
    t0 = time.time()
    calls(rec, devd_launcher.MAX_TRACED_CALLS)
    assert time.time() - t0 < 0.2
    for _ in range(200):
        if rec.trace_result:
            break
        time.sleep(0.05)
    first = rec.trace_result
    assert first["ok"] and first["traced_calls"] == devd_launcher.MAX_TRACED_CALLS
    assert rec.stop_trace(devd_launcher.FIRST_CALL_WAIT_S) == first   # the harness's
    calls(rec, 1)                                                     # no second stop
    assert rec.profiler.events.count("stop") == 1


@pytest.mark.parametrize("case", ["none_before_the_close", "twelve_before_it",
                                  "one_only_after_it"])
def test_the_harness_stop_waits_for_a_first_call(tmp_path, case):
    """Where the stretch holds a call at the harness's stop, the stop is at
    once; where it holds none, it waits until one lands, and where none
    comes within the bound it stops all the same with 0 calls (the harness
    then ends the run with no result)."""
    wait = devd_launcher.FIRST_CALL_WAIT_S
    after = {"none_before_the_close": (), "twelve_before_it": (),
             "one_only_after_it": (1.3, 1.4)}[case]
    rec, clock = recorder(calls_at=after)
    rec.start_trace(str(tmp_path))
    if case == "twelve_before_it":
        stopper = threading.Thread(target=calls,
                                   args=(rec, devd_launcher.MAX_TRACED_CALLS))
        stopper.start()
        stopper.join()
        for _ in range(200):
            if rec.trace_result:
                break
            time.sleep(0.01)
    out = rec.stop_trace(wait)
    assert out["ok"] and rec.profiler.events.count("stop") == 1
    if case == "none_before_the_close":
        assert out["traced_calls"] == 0
        assert out["waited_for_call_s"] == pytest.approx(wait, abs=0.05)
    elif case == "twelve_before_it":
        assert out["traced_calls"] == devd_launcher.MAX_TRACED_CALLS
        assert clock.t == 0.0                   # no wait at all
    else:
        # the first call after the close ends the wait; the trace holds it
        assert out["traced_calls"] == 1
        assert 1.3 <= out["waited_for_call_s"] < 1.3 + 2 * devd_launcher.POLL_S
    assert rec.traced_calls() is None           # stopped


def test_the_stop_op_waits_for_a_first_call():
    assert 1.0 <= devd_launcher.FIRST_CALL_WAIT_S <= 10.0
    seen = []

    class Rec:
        def stop_trace(self, wait_s=0.0):
            seen.append(wait_s)
            return {"ok": True}

    assert devd_launcher.handle({"op": "stop_trace"}, Rec())["ok"]
    assert seen == [devd_launcher.FIRST_CALL_WAIT_S]
