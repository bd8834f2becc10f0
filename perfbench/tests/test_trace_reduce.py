"""The reduction from a trace to idle share, kernel time and gaps: on a
hand-made trace whose answers are known, and on the small trace recorded
on the chip (data/trace_small.xplane.pb.gz, and data/trace_small.json:
what `trace_reduce.extract` took from it, with the launcher's spans)."""

import json
import os

import pytest

from harness import peaks, trace_reduce, verify_cost

HERE = os.path.dirname(os.path.abspath(__file__))


def synthetic():
    s = 1_000_000_000  # 1 s in ns
    wall0 = 1_790_000_000 * s
    # trace clock = wall - wall0 + 5 s; the marks bound the window [5 s, 7 s]
    mods = [["jit__verify_comb_impl(123)", 5.1 * s, 0.3 * s],
            ["jit__build_tables_impl(9)", 6.0 * s, 0.5 * s]]
    ex = {"devices": [{"name": "/device:TPU:0", "op_line": "XLA Ops",
                       "op_events": 3, "busy_ns": 0.8 * s,
                       "stretches": [[5.1 * s, 5.4 * s], [6.0 * s, 6.5 * s]],
                       "modules": mods}],
          "marks": [[wall0, 5.0 * s], [wall0 + 2 * s, 7.0 * s]],
          "window": [5.0 * s, 7.0 * s]}
    spans = [(wall0 + int(0.05 * s), wall0 + int(0.45 * s), 1000)]
    compiles = [(wall0 + int(1.0 * s), 0.2)]
    return ex, spans, compiles


def test_synthetic_trace_reduces_to_known_numbers():
    ex, spans, compiles = synthetic()
    r = trace_reduce.reduce(ex, spans, compiles, kernel_pattern="_verify_comb_impl")
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(2.0)
    assert r["busy_s"] == pytest.approx(0.8)
    assert r["kernel_s"] == pytest.approx(0.3) and r["kernel_events"] == 1
    assert r["device_ops"][0] == ["jit__build_tables_impl", pytest.approx(0.5)]
    gaps = dict((k, v) for k, v in r["idle_gaps"] if k.startswith("all_gaps:"))
    # idle 1.2 s in all: [5.0,5.1] [5.4,6.0] [6.5,7.0]; the span covers
    # [5.05,5.45] -> 0.05 + 0.05 idle in flight; the compile [5.8,6.0]
    assert gaps["all_gaps:chunk_in_flight_host_side"] == pytest.approx(0.1)
    assert gaps["all_gaps:compiling"] == pytest.approx(0.2)
    assert gaps["all_gaps:no_request_at_daemon"] == pytest.approx(0.9)
    assert len(r["idle_gaps"]) <= 10


def test_extract_reduces_a_real_xplane_file(tmp_path):
    """Stage 1 on the small .xplane.pb recorded on the chip (one traced
    stretch of the daemon under the benchmark's launcher: two 8-lane
    batches, 44,310 device events), and stage 2 on what it gives: the same
    numbers as the recorded extraction beside it. Stage 1 runs as the
    harness runs it, a script in a process of its own: this one stays off
    JAX, as a harness process must (`procs.no_jax_here`)."""
    import gzip
    import shutil
    import subprocess
    import sys

    tdir = tmp_path / "trace" / "plugins" / "profile" / "1"
    tdir.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data", "trace_small.xplane.pb.gz"), "rb") as f, \
            open(tdir / "trace_small.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    out = tmp_path / "extracted.json"
    r = subprocess.run([sys.executable, trace_reduce.__file__, str(tmp_path / "trace"),
                        str(out)], env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=200)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(out) as f:
        ex = json.load(f)
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        rec = json.load(f)
    assert ex["window"] == rec["extracted"]["window"]
    assert ex["devices"][0]["busy_ns"] == rec["extracted"]["devices"][0]["busy_ns"]
    assert ex["devices"] and ex["devices"][0]["name"].startswith("/device:TPU")
    assert len(ex["marks"]) == 2 and ex["window"][1] > ex["window"][0]
    dev = ex["devices"][0]
    assert dev["op_events"] > 1000 and dev["modules"]
    r = trace_reduce.reduce(ex, kernel_pattern="_verify_comb_impl")
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["kernel_s"] and r["kernel_events"] >= 1
    # the operations lie inside the programs that hold them
    assert r["busy_s"] <= sum(v for _k, v in r["device_ops"]) * 1.0001


def test_recorded_chip_trace_reduces():
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path) as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["extracted"], rec.get("spans"), rec.get("compiles"),
                            kernel_pattern=rec["kernel_pattern"])
    want = rec["expected"]
    assert r["devices"] == want["devices"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["kernel_s"] == pytest.approx(want["kernel_s"], rel=1e-9)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] <= r["kernel_s"] * 1.0001   # ops lie inside their program
    idle = sum(v for k, v in r["idle_gaps"] if k.startswith("all_gaps:"))
    # the listed gaps leave out the sub-microsecond ones between operations
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-3)
    assert idle <= r["window_s"] - r["busy_s"]
    # the roofline share that follows can never pass 100 %
    pk = peaks.peaks_for("TPU v5 lite")
    least, _ = verify_cost.least_seconds(rec["lanes"], rec["message_bytes"], 1000, pk)
    assert 0.0 < 100.0 * least / r["kernel_s"] < 100.0


def test_window_busy_carries_the_stretch_over_the_whole_window():
    """Ten calls in a 10 s window, two of them traced: narrow calls count
    at the traced narrow call's device time, the wide call (no wide call
    was traced) at the mean of all traced calls."""
    s = 1_000_000_000
    wall0 = 1_790_000_000 * s
    ex = {"devices": [{"name": "/device:TPU:0", "busy_ns": 0.006 * s, "stretches": [],
                       "modules": [["k(1)", 5.010 * s, 0.002 * s],
                                   ["k(1)", 5.011 * s + 0.002 * s, 0.004 * s]]}],
          "marks": [[wall0 + 9 * s, 5.0 * s], [wall0 + 9 * s + s // 10, 5.1 * s]],
          "window": [5.0 * s, 5.1 * s]}
    # two overlapping calls inside the stretch: 1 lane and 3 lanes; the
    # device served them in order
    traced = [(wall0 + 9 * s + 5_000_000, wall0 + 9 * s + 13_000_000, 1),
              (wall0 + 9 * s + 6_000_000, wall0 + 9 * s + 19_000_000, 3)]
    earlier = [(wall0 + k * s, wall0 + k * s + 10_000_000, 2) for k in range(7)]
    wide = [(wall0 + 8 * s, wall0 + 8 * s + 20_000_000, 70)]
    r = trace_reduce.window_busy(ex, earlier + wide + traced, wall0,
                                 wall0 + 10 * s, [8, 16, 32, 64, 128])
    assert r["calls"] == 10 and r["calls_traced"] == 2
    assert r["calls_of_traced_widths"] == 9
    assert r["device_ms_by_width"] == {"8": pytest.approx(3.0)}
    assert r["busy_s"] == pytest.approx(10 * 0.003)
    assert r["in_flight_s"] == pytest.approx(7 * 0.010 + 0.020 + 0.014)
    assert trace_reduce.window_busy(ex, earlier, wall0, wall0 + 10 * s, [8]) is None


def test_window_busy_on_the_recorded_chip_trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        rec = json.load(f)
    spans = [tuple(x) for x in rec["spans"]]
    lo = spans[0][0] - 1_000_000
    r = trace_reduce.window_busy(rec["extracted"], spans, lo, lo + 100_000_000, [8, 16])
    assert r["calls"] == r["calls_traced"] == 2
    assert r["busy_s"] == pytest.approx(rec["expected"]["kernel_s"], rel=1e-6)
    assert r["busy_s"] < r["in_flight_s"] < r["window_s"]
