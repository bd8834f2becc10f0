"""The readers of what the program itself writes when its processes stop
(PR 25): the daemon's per-call records, node 0's stop dump, and the
daemon's annotations in the profiler's trace. Each on a small recorded
file under data/ (cut from a CPU rehearsal at 10 writes/s; the trace join
also on a hand-made trace whose answers are known), the metric files
against BENCHMARK.json, and one whole traced rehearsal that has to print
every new metric with a value."""

import importlib
import io
import json
import os
import shutil
import statistics
from contextlib import redirect_stdout

import pytest
from conftest import BENCH, ROOT

from harness import artifacts, trace_annotations
from harness.observe import Observations

DATA = os.path.join(BENCH, "tests", "data")
NEW = [f"daemon_{p}_ms_p50" for p in artifacts.PHASES] + [
    "daemon_calls_in_flight_mean", "daemon_verdict_lag_ms_p50",
    "node_verify_wait_ms_per_height_p50",
    "node_verify_ipc_ms_per_height_p50",
    "height_propose_ms_p50", "height_votes_ms_p50",
    "height_commit_tail_ms_p50"]


def load(path):
    with open(path) as f:
        return json.load(f)


def read(metric, obs):
    spec = load(os.path.join(BENCH, "metrics", metric + ".json"))
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(obs, spec.get("params", {}), {})


@pytest.fixture
def obs(tmp_path):
    """A run's directory as a traced run leaves it, from the small files."""
    run = tmp_path / "run"
    (run / "trace").mkdir(parents=True)
    (run / "node0" / "flightrec").mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "spans_small.jsonl"), run / "devd.spans.jsonl")
    shutil.copy(os.path.join(DATA, "stop_dump_small.json"),
                run / "node0" / "flightrec" / "dump-20261001T000000-stop.json")
    win = load(os.path.join(DATA, "call_records_window.json"))
    o = Observations(window_s=win["window_s"], open_wall=win["open_wall"])
    o.trace = {"dir": str(run / "trace")}
    return o


def small_records():
    with open(os.path.join(DATA, "spans_small.jsonl")) as f:
        head = json.loads(f.readline())
        return head, [dict(zip(head["fields"], json.loads(x))) for x in f]


def test_phase_percentiles_over_the_windows_eight_wide_calls(obs):
    head, recs = small_records()
    lo, hi = artifacts.window_ns(obs)
    mine = [r for r in recs if lo <= r["t_recv0"] < hi and r["width"] == 8]
    assert 40 <= len(mine) < len(recs)   # the warm-up calls lie before it
    ends = ["t_recv0", "t_decoded", "t_marshalled", "t_dispatched",
            "t_verdicts", "t_replied"]
    total = 0.0
    for i, phase in enumerate(artifacts.PHASES):
        want = statistics.median((r[ends[i + 1]] - r[ends[i]]) / 1e6 for r in mine)
        got = read(f"daemon_{phase}_ms_p50", obs)
        assert got == pytest.approx(want, rel=1e-9) and got > 0
        total += got
    whole = statistics.median((r["t_replied"] - r["t_recv0"]) / 1e6 for r in mine)
    assert total == pytest.approx(whole, rel=0.25)   # medians, not a partition


def test_in_flight_mean_over_the_windows_calls(obs):
    _head, recs = small_records()
    lo, hi = artifacts.window_ns(obs)
    mine = [r["in_flight_at_recv"] for r in recs if lo <= r["t_recv0"] < hi]
    assert read("daemon_calls_in_flight_mean", obs) == pytest.approx(
        sum(mine) / len(mine))


def test_per_height_percentiles_from_the_stop_dump(obs):
    traces = load(os.path.join(DATA, "stop_dump_small.json"))["consensus_traces"]
    lo = obs.open_wall
    mine = [t for t in traces if lo <= t["started_at"] < lo + obs.window_s]
    assert 3 <= len(mine) < len(traces)
    want = statistics.median(1000 * t["aux"]["verify_wait_s"] for t in mine)
    assert read("node_verify_wait_ms_per_height_p50", obs) == pytest.approx(want)
    assert read("node_verify_ipc_ms_per_height_p50", obs) <= want
    parts = [read(f"height_{k}_ms_p50", obs)
             for k in ("propose", "votes", "commit_tail")]
    assert all(p >= 0 for p in parts) and parts[1] > 0
    # with new_height the three groups hold every segment: they partition
    # each height's wall clock within the trace contract's 5%
    names = set()
    for k in ("propose", "votes", "commit_tail"):
        names |= set(load(os.path.join(BENCH, "metrics",
                                       f"height_{k}_ms_p50.json"))["params"]["segments"])
    from tendermint_tpu.consensus.trace import SEGMENTS

    assert names | {"new_height"} == set(SEGMENTS)
    for t in mine:
        total = sum(t["segments"].values())
        assert abs(total - t["wall_s"]) <= max(0.05 * t["wall_s"], 0.005)
        assert t["aux"]["verify_wait_s"] <= t["wall_s"]


def synthetic_trace():
    ms = 1_000_000.0
    ann = []
    # three calls; seq 3 dispatched while seq 2 was on the device
    for seq, d0, d1, w1 in ((1, 10.0, 10.5, 12.4), (2, 20.0, 20.4, 22.3),
                            (3, 20.6, 21.0, 24.2)):
        ann += [["decode", seq, 0, (d0 - 0.7) * ms, (d0 - 0.6) * ms],
                ["marshal", seq, 2, (d0 - 0.6) * ms, d0 * ms],
                ["dispatch", seq, 2, d0 * ms, d1 * ms],
                ["device_wait", seq, 2, d1 * ms, w1 * ms],
                ["reply", seq, 2, w1 * ms, (w1 + 0.1) * ms]]
    ann.append(["decode", 4, 0, 30.0 * ms, 30.1 * ms])   # cut off by the stop
    mods = [["jit__verify_comb_impl(1)", 10.6 * ms, 12.3 * ms],
            ["jit__verify_comb_impl(1)", 20.5 * ms, 22.2 * ms],
            ["jit__verify_comb_impl(1)", 22.3 * ms, 24.0 * ms],
            ["jit__build_tables_impl(2)", 25.0 * ms, 26.0 * ms]]
    return {"annotations": ann, "clocks": [[1, 1.0], [2, 40.0 * ms]],
            "modules": mods, "host_exec": []}


def test_verdict_lag_on_a_hand_made_trace():
    ex = synthetic_trace()
    calls = trace_annotations.joined(ex, "_verify_comb_impl")
    assert [c["seq"] for c in calls] == [1, 2, 3]
    # every program starts after its call's dispatch began and ends before
    # its device_wait ended: one clock, no offset
    for c in calls:
        assert c["dispatch_start"] <= c["program_start"] < c["program_end"] <= c["wait_end"]
    # seq 3's stretch holds the second program too (it ends inside it):
    # that one is seq 2's, given away first
    assert calls[2]["program_start"] == pytest.approx(22.3e6)
    lags = trace_annotations.verdict_lags(ex, "_verify_comb_impl")
    assert lags == pytest.approx([0.1, 0.1, 0.2])
    assert trace_annotations.verdict_lags(ex, "no_such_kernel") == []


def test_verdict_lag_on_the_trace_recorded_on_the_chip():
    """data/annotations_small.json: what `trace_annotations.extract` took
    from the traced stretch of a `net4.steady` run on the chip (PR 25,
    seed 2147483901: 12 calls under the launcher's trace). Every program
    of the kernel but the one whose call began before the trace did lies
    inside one call's dispatch..device_wait, on the trace's own clock."""
    ex = load(os.path.join(DATA, "annotations_small.json"))
    progs = [m for m in ex["modules"] if "_verify_comb_impl" in m[0]]
    calls = trace_annotations.joined(ex, "_verify_comb_impl")
    assert len(progs) == 13 and len(calls) == 12
    assert len({c["seq"] for c in calls}) == 12
    assert len({c["program_start"] for c in calls}) == 12
    for c in calls:
        assert c["dispatch_start"] < c["program_start"] < c["program_end"] < c["wait_end"]
        assert 1.6e6 < c["program_end"] - c["program_start"] < 1.8e6   # 1.70 ms
    lags = trace_annotations.verdict_lags(ex, "_verify_comb_impl")
    assert statistics.median(lags) == pytest.approx(2.1806, abs=1e-3)
    assert min(lags) > 0.3 and max(lags) < 4.0


def test_verdict_lag_reader_reads_the_trace_once(obs):
    obs.trace["annotations"] = synthetic_trace()   # as read_annotations leaves it
    assert read("daemon_verdict_lag_ms_p50", obs) == pytest.approx(0.1)
    obs.trace["annotations"] = {"annotations": [], "modules": [], "host_exec": []}
    assert read("daemon_verdict_lag_ms_p50", obs) is None


def test_a_cpu_trace_takes_the_executor_threads_for_the_device():
    ex = synthetic_trace()
    ex["host_exec"] = [[s, e] for _n, s, e in ex.pop("modules")[:3]]
    ex["modules"] = []
    lags = trace_annotations.verdict_lags(ex, "_verify_comb_impl")
    assert lags[0] == pytest.approx(0.1) and len(lags) == 3


def test_annotations_are_extracted_from_a_real_xplane_file(tmp_path):
    """The trace PR 24 recorded on the chip has the device's programs and
    none of the daemon's annotations (it is from before them): the
    extraction holds the one, the reader gives nothing."""
    import gzip
    import subprocess
    import sys

    tdir = tmp_path / "trace" / "plugins" / "profile" / "t"
    tdir.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "trace_small.xplane.pb.gz"), "rb") as f, \
            open(tdir / "vm.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    out = tmp_path / "ann.json"
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "harness", "trace_annotations.py"),
         str(tmp_path / "trace"), str(out)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    ex = load(out)
    assert ex["annotations"] == [] and ex["clocks"] == []
    assert any("_verify_comb_impl" in m[0] for m in ex["modules"])
    assert trace_annotations.verdict_lags(ex, "_verify_comb_impl") == []


def test_a_missing_file_raises_with_the_path_it_looked_for(obs):
    run = artifacts.run_dir(obs)
    os.remove(os.path.join(run, "devd.spans.jsonl"))
    with pytest.raises(FileNotFoundError, match="devd.spans.jsonl"):
        read("daemon_marshal_ms_p50", obs)
    shutil.rmtree(os.path.join(run, "node0", "flightrec"))
    with pytest.raises(FileNotFoundError, match="flightrec"):
        read("height_votes_ms_p50", obs)


def test_the_sockets_fallback_directory_is_read_from_the_daemons_log(obs, tmp_path):
    run = artifacts.run_dir(obs)
    far = tmp_path / "perfbench-xyz"
    far.mkdir()
    shutil.move(os.path.join(run, "devd.spans.jsonl"), far / "devd.spans.jsonl")
    with open(os.path.join(run, "devd.log"), "w") as f:
        f.write("2026-10-01 06:00:00,000 devd INFO devd listening on "
                f"{far}/devd.sock (pid 7)\n")
    assert artifacts.spans_path(run) == str(far / "devd.spans.jsonl")
    assert read("daemon_reply_ms_p50", obs) > 0


def test_a_wrapped_ring_is_refused(obs):
    path = os.path.join(artifacts.run_dir(obs), "devd.spans.jsonl")
    lines = open(path).read().splitlines()
    head = json.loads(lines[0])
    head["count"] = head["ring_size"] + 1
    with open(path, "w") as f:
        f.write("\n".join([json.dumps(head)] + lines[1:]) + "\n")
    with pytest.raises(RuntimeError, match="wrapped"):
        read("daemon_decode_ms_p50", obs)


def test_a_program_from_before_the_records_yields_nothing(obs, monkeypatch):
    """The parent commit, measured under this PR's benchmark files, writes
    neither file: the readers leave their metrics out and do not raise."""
    shutil.rmtree(os.path.join(artifacts.run_dir(obs), "node0"))
    os.remove(os.path.join(artifacts.run_dir(obs), "devd.spans.jsonl"))
    monkeypatch.setattr(artifacts, "program_keeps_records", lambda: False)
    obs.trace["annotations"] = {"annotations": [], "modules": [], "host_exec": []}
    assert [read(m, obs) for m in NEW] == [None] * len(NEW)


def test_every_new_metric_file_names_a_reader_and_an_entry():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec = load(os.path.join(BENCH, "metrics", name + ".json"))
        entry = entries[name]
        assert {k: spec[k] for k in entry} == entry
        assert "net4.steady" in entry["workloads"] and entry["better"] == "lower"
        assert "by_workload" not in spec
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        assert hasattr(importlib.import_module("readers." + spec["reader"]), "read")
    readers = {load(os.path.join(BENCH, "metrics", n + ".json"))["reader"] for n in NEW}
    assert readers == {"span_phase_percentile", "span_in_flight_mean",
                       "trace_verdict_lag", "dump_height_percentile"}


def test_a_traced_rehearsal_prints_every_new_metric_with_a_value():
    """Slow (boots four nodes and a CPU daemon, about a minute). The
    traced stretch is lengthened so that it holds whole calls at this
    size; nothing else differs from the fault tests' runs."""
    import run as bench_run

    scale = {"traffic": {"rate_per_s": 10, "signers": 6, "lead_in_s": 1.0,
                         "readback_sample": 12, "forged_writes": 4,
                         "trace_window_s": 1.5},
             "config": {"daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                           "TENDERMINT_DEVD_WARM": ""},
                                   "warm_buckets": [8, 16], "warm_passes": 2}}}
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", "net4.steady", "--seed", str(2**31 + 25),
                             "--seconds", "6", "--trace", "1", "--rehearsal",
                             "--scale", json.dumps(scale)])
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, {k: v for k, v in line["compared"].items()
                                     if v["value"] > v["limit"]}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert [m for m in NEW if m not in got] == []
    assert all(isinstance(got[m], float) and got[m] >= 0 for m in NEW)
    phases = sum(got[f"daemon_{p}_ms_p50"] for p in artifacts.PHASES)
    assert phases > 0 and got["height_votes_ms_p50"] > 0
    # the daemon's ring and node 0's stop dump are where the issue says
    run_dir = os.path.join(ROOT, ".perfbench_run", "net4.steady")
    assert os.path.exists(os.path.join(run_dir, "devd.spans.jsonl"))
    assert artifacts.stop_dump(run_dir).endswith("-stop.json")
