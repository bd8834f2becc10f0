"""`committee.steady` (PR 27): its entries and data files, the three new
readers on hand-made files whose answers are known (and on a program that
writes none of what they read: nothing, no raise), the whole cell in
rehearsal at 7 validators on the CPU daemon, and the faults that only its
two own comparisons can catch.

The rehearsals boot real node processes and a daemon that compiles its
kernels for the CPU backend on first use: 1-3 minutes a case. Run with
    python3 -m pytest perfbench/tests/test_committee_cell.py -q
"""

import importlib
import io
import json
import os
from contextlib import redirect_stdout

import pytest
from conftest import BENCH, ROOT

from harness.observe import Observations

CELL = "committee.steady"
SMALL = {"config": {"validators": 7,
                    "daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                       "TENDERMINT_DEVD_WARM": "",
                                       "TENDERMINT_TPU_COMB_MIN_SIGHT": "1"},
                               "warm_buckets": [8, 16, 32, 64], "warm_passes": 1}},
         "traffic": {"rate_per_s": 5, "signers": 6, "lead_in_s": 1.0,
                     "readback_sample": 12, "forged_writes": 4}}
NEW_COUNTERS = ["daemon_lanes_per_call_mean", "commit_verify_ms_per_height_p50",
                "node_cpu_ms_per_height_p50", "fleet_cpu_share",
                "votes_batched_share"]


def load(path):
    with open(path) as f:
        return json.load(f)


def read(metric, obs):
    spec = load(os.path.join(BENCH, "metrics", metric + ".json"))
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(obs, spec.get("params", {}), {})


# -- the entries ----------------------------------------------------------------


def test_the_cell_its_configuration_and_its_traffic():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert (cell[0]["config"], cell[0]["traffic"]) == ("committee-signedkv",
                                                       "writes-committee")
    [entry] = [c for c in b["configs"] if c["name"] == "committee-signedkv"]
    assert entry["reduced"] == ["validators"]
    cfg = load(os.path.join(ROOT, entry["file"]))
    net4 = load(os.path.join(BENCH, "configs", "net4-signedkv.json"))
    # nothing but the committee differs from the deployment in the benchmark:
    # no time-out, width, environment or backend
    for key in ("app", "consensus", "base", "node_env", "daemon",
                "injected_message_delay_ms", "chips"):
        assert cfg[key] == net4[key], key
    assert cfg["deployment"] == "committee_net"
    assert 16 <= cfg["validators"] <= 32 and cfg["validators_published"] == 100
    assert set(cfg["reduced_note"]) == set(cfg["reduced"]) == {"validators"}
    assert set(cfg["guarantees"]) == set(net4["guarantees"])
    mix = load(os.path.join(BENCH, "traffic", "writes-committee.json"))
    steady = load(os.path.join(BENCH, "traffic", "writes-steady.json"))
    assert mix["rate_per_s"] in (10, 20) and mix["readback_sample"] == 100
    for key in ("kind", "arrivals", "method", "targets", "signers", "lead_in_s",
                "request_timeout_s", "forged_writes", "trace_window_s"):
        assert mix[key] == steady[key], key
    for m in b["end_to_end"]:
        if m["name"].startswith("commit_latency"):
            assert {"net4.steady", CELL} <= set(m["workloads"])


def test_every_committee_metric_has_its_entry_its_file_and_its_reader():
    import run as bench_run

    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    # the cell's metrics are those it read before the fold, and the
    # frames per wake of its nodes' I/O loops
    fold = load(os.path.join(BENCH, "tests", "data", "per_layer_fold.json"))
    before = {r["new"] for r in fold["entries"] if r["cell"] == CELL}
    assert len(before) == 29
    assert {m["name"] for m in mine} == before | {"p2p_io_frames_per_wake"}
    for m in mine:
        assert m["moves"] in ("commit_latency_p50_ms", "commit_latency_p95_ms")
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert {k: spec[k] for k in m} == m
        reader, _params = bench_run.metric_reader(spec, CELL)
        assert hasattr(importlib.import_module("readers." + reader), "read")
        if "net4.steady" in m["workloads"]:   # same reader, same way of reading
            assert bench_run.metric_reader(spec, "net4.steady") == \
                bench_run.metric_reader(spec, CELL)
    assert {m["name"] for m in mine} >= set(NEW_COUNTERS)
    # the three daemon phases are read at one width, which the files state
    widths = {load(os.path.join(BENCH, "metrics", f"daemon_{p}_ms_p50.json"))
              ["params"]["width"] for p in ("marshal", "dispatch", "device_wait")}
    assert len(widths) == 1 and widths <= {8, 16, 32, 64, 128, 256}


# -- the new readers -------------------------------------------------------------

OPEN = 1_000_000.0


def make_run(tmp_path, nodes, records=None, fields_extra=True):
    run = tmp_path / "run"
    (run / "trace").mkdir(parents=True)
    for i, traces in enumerate(nodes):
        d = run / f"node{i}" / "flightrec"
        d.mkdir(parents=True)
        with open(d / "dump-20261001T000000-stop.json", "w") as f:
            json.dump({"consensus_traces": traces}, f)
    if records is not None:
        fields = ["seq", "conn", "op", "lanes", "width", "t_recv0", "t_decoded",
                  "t_marshalled", "t_dispatched", "t_verdicts", "t_replied",
                  "in_flight_at_recv", "rid"]
        if fields_extra:
            fields += ["program", "merged", "merged_conns", "program_lanes"]
        with open(run / "devd.spans.jsonl", "w") as f:
            f.write(json.dumps({"fields": fields, "count": len(records),
                                "ring_size": 65536}) + "\n")
            for r in records:
                f.write(json.dumps(r[:len(fields)]) + "\n")
    o = Observations(window_s=10.0, open_wall=OPEN)
    o.trace = {"dir": str(run / "trace")}
    return o


def height(at, **aux):
    return {"height": 1, "started_at": OPEN + at, "aux": aux, "segments": {}}


def record(seq, lanes, program, merged, program_lanes, at=1.0):
    t = int((OPEN + at) * 1e9)
    return [seq, seq, "verify", lanes, 8, t, t + 1, t + 2, t + 3, t + 4, t + 5, 0,
            f"r-{seq}", program, merged, merged, program_lanes]


def test_new_readers_on_files_whose_answers_are_known(tmp_path):
    nodes = [
        [height(1, cpu_s=0.2, commit_verify_s=0.030, votes_received=60, votes_batched=40),
         height(2, cpu_s=0.4, commit_verify_s=0.050, votes_received=60, votes_batched=50),
         height(3, cpu_s=0.3, commit_verify_s=0.040, votes_received=60, votes_batched=30),
         height(11, cpu_s=9.0, commit_verify_s=9.0)],            # past the window
        [height(1, cpu_s=1.0, votes_received=20, votes_batched=0),
         height(-1, cpu_s=9.0)],                                 # before it
    ]
    records = [record(1, 1, 1, 1, 1), record(2, 4, 2, 3, 36), record(3, 16, 2, 3, 36),
               record(4, 16, 2, 3, 36), record(5, 7, 5, 1, 7),
               record(6, 200, 6, 1, 200, at=20.0)]               # past the window
    obs = make_run(tmp_path, nodes, records)
    assert read("node_cpu_ms_per_height_p50", obs) == pytest.approx(300.0)
    assert read("commit_verify_ms_per_height_p50", obs) == pytest.approx(40.0)
    # (0.2 + 0.4 + 0.3 + 1.0) core-seconds of 13 cores x 10 s
    assert read("fleet_cpu_share", obs) == pytest.approx(100 * 1.9 / 130)
    assert read("votes_batched_share", obs) == pytest.approx(100 * 120 / 200)
    # three programs in the window: 1, 36 and 7 lanes
    assert read("daemon_lanes_per_call_mean", obs) == pytest.approx(44 / 3)


def test_new_readers_read_nothing_from_a_program_without_the_counters(tmp_path):
    """The parent commit these metrics are first measured beside: heights
    without the notes, records without the program's fields."""
    nodes = [[height(1, verify_wait_s=0.05), height(2, verify_wait_s=0.04)]]
    records = [record(1, 1, 1, 1, 1), record(2, 4, 2, 1, 4)]
    obs = make_run(tmp_path, nodes, records, fields_extra=False)
    for metric in NEW_COUNTERS:
        assert read(metric, obs) is None, metric


# -- the whole cell, in rehearsal ---------------------------------------------------


def run_cell(scale, seconds, trace=0, control="", patch=None, monkeypatch=None):
    import run as bench_run
    from harness import rpc

    if patch is not None:
        real = rpc.call

        def call(addr, method, params=None, timeout=10.0):
            return patch(method, params, real(addr, method, params, timeout))

        monkeypatch.setattr(rpc, "call", call)
    argv = ["--workload", CELL, "--seed", str(2**31 + 127), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearsal",
            "--scale", json.dumps(scale)]
    if control:
        argv += ["--control", control]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    return line, over


def test_committee_of_seven_is_correct_by_all_twelve_comparisons():
    line, over = run_cell(SMALL, 8)
    assert line["correct"] is True and not over
    assert len(line["compared"]) == 12
    assert list(line["compared"])[-2:] == ["commits_failing_plain_quorum",
                                           "nodes_with_host_verified_sigs"]
    assert all(c["limit"] == 0 for c in line["compared"].values())
    assert line["attempted"] == 40 and line["failed"] == 0
    notes = line["notes"]
    assert notes["commit_heights_checked"] >= 1 and notes["nodes_on_host"] == []
    assert "node0_heights_without_batched_vote" in notes
    assert set(line["metrics"]) == {"commit_latency_p50_ms", "commit_latency_p95_ms",
                                    "setup_s"}


def test_traced_rehearsal_prints_the_metrics_of_the_new_counters():
    line, _over = run_cell(SMALL, 8, trace=1)
    for metric in NEW_COUNTERS:
        assert metric in line["metrics"], metric
    assert line["metrics"]["daemon_lanes_per_call_mean"]["value"] > 1.0
    assert 0 < line["metrics"]["fleet_cpu_share"]["value"]
    assert 0 <= line["metrics"]["votes_batched_share"]["value"] <= 100


def test_commit_with_votes_removed_under_quorum_is_caught(monkeypatch):
    """What node 0's `commit` RPC gives has lost precommits down to two
    thirds of the power exactly: the plain reference has to say so."""
    def thin(method, params, res):
        if method == "commit" and res.get("commit"):
            pcs = res["commit"]["precommits"]
            keep = (2 * len(pcs)) // 3          # 4 of 7: under quorum
            held = [i for i, p in enumerate(pcs) if p is not None]
            for i in held[keep:]:
                pcs[i] = None
        return res

    line, over = run_cell(SMALL, 8, patch=thin, monkeypatch=monkeypatch)
    assert line["correct"] is False
    assert over == {"commits_failing_plain_quorum"}
    c = line["compared"]["commits_failing_plain_quorum"]
    assert c["value"] == line["notes"]["commit_heights_checked"] >= 1


def test_nodes_forced_onto_the_host_path_are_caught():
    """Every node told to keep off the device (the environment a node that
    missed the daemon at boot ends up in): the chain is the same chain, and
    only the comparison that looks at where signatures were checked reads
    over its limit."""
    scale = json.loads(json.dumps(SMALL))
    scale["config"]["node_env"] = {"TENDERMINT_TPU_DISABLE": "1",
                                   "TENDERMINT_TPU_MIN_BATCH": "1"}
    line, over = run_cell(scale, 8)
    assert line["correct"] is False
    assert "nodes_with_host_verified_sigs" in over
    assert line["compared"]["nodes_with_host_verified_sigs"]["value"] == 7
    assert "commits_failing_plain_quorum" not in over


def test_control_accept_all_reads_over_its_limit():
    line, over = run_cell(SMALL, 8, control="accept-all")
    assert line["correct"] is False
    assert "forged_writes_accepted" in over


def test_control_half_batch_is_never_correct():
    """A verifier that answers True for the second half of every batch:
    a forged write alone in its batch is accepted at CheckTx. Once it is
    in a block the nodes' DeliverTx verdicts depend on which half of a
    merged program it rode, the app hashes part and the net stops (on the
    chip at 16 validators: no result, PERF.md PR 27). Either way the run
    does not come out correct."""
    import run as bench_run

    argv = ["--workload", CELL, "--seed", str(2**31 + 131), "--seconds", "8",
            "--trace", "0", "--rehearsal", "--scale", json.dumps(SMALL),
            "--control", "half-batch"]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    if rc == 0:
        line = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert line["correct"] is False
        assert any(v["value"] > v["limit"] for v in line["compared"].values())
    else:
        assert buf.getvalue().strip() == ""      # no result line at all
