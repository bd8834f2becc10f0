"""`burst.drain` (BASELINE config 5 under upstream's limits): its entries
and data files, and the whole cell in rehearsal on the CPU daemon at a
burst of 200, judged by `reference/burst_ref.py`; the control verifier
that skips verification must come out as not correct.

The rehearsals boot real node processes and a daemon that compiles its
kernels for the CPU backend on first use: 2-4 minutes a case. Run with
    python3 -m pytest perfbench/tests/test_burst_cell.py -q
"""

import io
import json
import os
from contextlib import redirect_stdout

from conftest import BENCH, ROOT

CELL = "burst.drain"
SMALL = {"config": {"burst_writes": 200,
                    "daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                       "TENDERMINT_DEVD_WARM": "",
                                       "TENDERMINT_TPU_COMB_MIN_SIGHT": "1"},
                               "warm_buckets": [8, 16, 32, 64, 128, 256],
                               "warm_passes": 1}},
         "traffic": {"connections_per_node": 8, "lead_in_s": 1.0,
                     "bursts_at_s": [0.0, 4.0, 8.0],
                     "answer_after_close_s": 10.0, "readback_sample": 20}}


BURST_METRICS = [
    "daemon_compiles_in_window", "rounds_over_zero", "height_interval_ms_mean",
    "height_propose_ms_p50", "daemon_lanes_per_call_mean", "committed_writes_per_s",
    "block_txs_max", "sig_gate_lanes_per_batch_mean", "apply_verify_ms_p50",
    "apply_app_ms_p50", "block_parts_ms_p50", "p2p_io_frames_per_wake"]


def load(path):
    with open(path) as f:
        return json.load(f)


def test_the_cell_its_configuration_and_its_traffic():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    [cell] = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "burst-signedkv", "writes-burst", 1)
    [entry] = [c for c in b["configs"] if c["name"] == "burst-signedkv"]
    cfg = load(os.path.join(ROOT, entry["file"]))
    assert cfg["deployment"] == "burst_net"
    assert entry["reduced"] == cfg["reduced"]
    mix = load(os.path.join(BENCH, "traffic", "writes-burst.json"))
    steady = load(os.path.join(BENCH, "traffic", "writes-steady.json"))
    assert mix["lead_in_rate_per_s"] == steady["rate_per_s"]
    assert mix["lead_in_s"] == steady["lead_in_s"]
    assert mix["method"] == "broadcast_tx_sync" and mix["forged_writes"] == 12
    assert mix["connections_per_node"] * cfg["validators"] \
        <= cfg["upstream_limits"]["rpc_max_inflight"]
    for m in b["end_to_end"]:
        if m["name"].startswith("commit_latency"):
            assert CELL in m["workloads"]
    # every reading of the cell is an entry that lists it
    burst = [m for m in b["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in burst} == set(BURST_METRICS)
    assert 1 <= len(b["per_layer"]) <= 128
    for m in burst:
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert {k: spec[k] for k in m} == m and "by_workload" not in spec


def run_cell(scale, seconds, control="", trace=0):
    import run as bench_run

    argv = ["--workload", CELL, "--seed", str(2**31 + 39), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearsal", "--scale",
            json.dumps(scale)]
    if control:
        argv += ["--control", control]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    return line, over


def test_rehearsal_run_is_correct():
    """Traced, so that the line carries the cell's per-layer metrics (a
    burst of 200 makes no block of several parts: no `block_parts_ms_p50`)."""
    line, over = run_cell(SMALL, 12, trace=1)
    assert line["correct"] is True and not over, over
    assert line["attempted"] == 188 and line["failed"] == 0
    notes = line["notes"]
    assert notes["forged_writes"] == 12 and notes["refused_valid_writes"] == {}
    assert notes["drain_s"] > 8 and sum(notes["block_txs"]) >= 188
    assert len(notes["drain_s_by_part"]) == 3 and notes["traced_calls"] >= 1
    r = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(BURST_METRICS) - set(r) == {"block_parts_ms_p50"}
    assert r["sig_gate_lanes_per_batch_mean"] >= 1 and r["p2p_io_frames_per_wake"] > 0
    assert r["committed_writes_per_s"] > 0 and r["rounds_over_zero"] == 0
    assert r["apply_verify_ms_p50"] > 0 and r["apply_app_ms_p50"] > 0
    assert r["block_txs_max"] == max(notes["block_txs"])
    assert {"commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"} <= set(
        line["end_to_end_of_this_traced_run"])
    # the warm-up's wide batches rode the stream, one record a chunk
    from harness import artifacts

    _, records = artifacts.load_spans(os.path.join(
        ROOT, ".perfbench_run", CELL, "devd.spans.jsonl"))
    streamed = [r for r in records if r["op"] == "verify_stream"]
    assert streamed and {r["width"] for r in streamed} >= {256}


def test_control_verifier_that_skips_verification():
    line, over = run_cell(SMALL, 8, control="accept-all")
    assert line["correct"] is False
    assert {"forged_writes_accepted", "forged_writes_in_chain"} <= over
    assert line["notes"]["forged_writes_accepted_n"] == 12
