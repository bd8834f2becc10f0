"""The launcher's trace bound: the verifier call that trips it returns at
once (the trace is written out on a thread of its own), and the harness's
later stop gets that first stop's answer."""

import time

from harness import devd_launcher


def test_trace_stops_by_itself_without_holding_the_call(tmp_path, monkeypatch):
    rec = devd_launcher.Recorder()
    stopping = []

    def slow_stop():
        stopping.append(time.time())
        time.sleep(0.5)          # stands for a minute of writing
        return real_stop()

    real_stop = rec.stop_trace
    monkeypatch.setattr(rec, "stop_trace", slow_stop)
    assert rec.start_trace(str(tmp_path))["ok"]
    t0 = time.time()
    for _ in range(devd_launcher.MAX_TRACED_CALLS):
        rec.add_span(time.time_ns(), time.time_ns() + 1000, 1)
    assert time.time() - t0 < 0.2 and len(stopping) == 1
    for _ in range(200):
        if rec.trace_result:
            break
        time.sleep(0.05)
    first = rec.trace_result
    assert first["ok"] and first["traced_calls"] == devd_launcher.MAX_TRACED_CALLS
    assert real_stop() == first          # the harness's own stop, afterwards
    rec.add_span(time.time_ns(), time.time_ns() + 1000, 1)   # no second stop
    assert len(stopping) == 1
