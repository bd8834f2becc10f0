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


def load(path):
    with open(path) as f:
        return json.load(f)


def test_the_cell_its_configuration_and_its_traffic():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    [cell] = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "burst-signedkv", "writes-burst", 1)
    assert b["workloads"][-1] == cell
    assert b["configs"][-1]["name"] == "burst-signedkv"
    cfg = load(os.path.join(ROOT, b["configs"][-1]["file"]))
    assert cfg["deployment"] == "burst_net"
    assert b["configs"][-1]["reduced"] == cfg["reduced"]
    mix = load(os.path.join(BENCH, "traffic", "writes-burst.json"))
    steady = load(os.path.join(BENCH, "traffic", "writes-steady.json"))
    assert mix["lead_in_rate_per_s"] == steady["rate_per_s"]
    assert mix["lead_in_s"] == steady["lead_in_s"]
    assert mix["method"] == "broadcast_tx_sync" and mix["forged_writes"] == 12
    assert mix["connections_per_node"] * cfg["validators"] \
        <= cfg["upstream_limits"]["rpc_max_inflight"]
    for m in b["end_to_end"]:
        if m["name"].startswith("commit_latency"):
            assert m["workloads"][-1] == CELL
    # the one entry that fits under the 128 `per_layer` may hold; the
    # cell's other readings are in its result line's notes
    burst = [m for m in b["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in burst] == ["daemon_compiles_in_window.burst"]
    assert b["per_layer"][-1:] == burst and len(b["per_layer"]) == 128
    for m in burst:
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert {k: spec[k] for k in m} == m


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
    line, over = run_cell(SMALL, 12)
    assert line["correct"] is True and not over, over
    assert line["attempted"] == 188 and line["failed"] == 0
    notes = line["notes"]
    assert notes["forged_writes"] == 12 and notes["refused_valid_writes"] == {}
    assert notes["drain_s"] > 8 and sum(notes["block_txs"]) >= 188
    assert len(notes["drain_s_by_part"]) == 3
    r = notes["readings"]
    assert r["sig_gate_lanes_per_batch_mean"] >= 1
    assert r["committed_writes_per_s"] > 0 and r["rounds_over_zero"] == 0
    assert r["apply_verify_ms_p50"] > 0 and r["apply_app_ms_p50"] > 0
    assert {"commit_latency_p50_ms", "commit_latency_p95_ms", "setup_s"} <= set(
        line["metrics"])
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
