"""`ycsb-b.steady`: its entries and data files, asserted by
membership; the reader of a read's road (`readers/read_road_percentile.py`)
on a synthetic run; the proof judge (`ycsb_verified_net.judge_proofs`)
on a synthetic generator record with planted faults; and the whole cell
in rehearsal at a small size on the CPU daemon (2-4 minutes). Run with
    python3 -m pytest perfbench/tests/test_ycsb_b_cell.py -q
"""

import importlib
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest
from conftest import BENCH, ROOT

CELL = "ycsb-b.steady"
READ_METRICS = {"read_edge_ms_p50": ("edge", 50), "read_edge_ms_p95": ("edge", 95),
                "read_lock_ms_p50": ("lock", 50),
                "read_lock_ms_p95": ("lock", 95), "read_prove_ms_p50": ("prove", 50),
                "read_reply_ms_p50": ("reply", 50)}
# the existing entries this cell reads as ycsb-a.steady does: every one of
# ycsb-a.steady's but the three whose files give that cell a reader of
# its own (`by_workload`), which a pool cell needs and this cell has not,
# and the one of MANY_PART_BLOCKS
SHARED = {"read_latency_ms_p95", "generator_late_ms_p95", "height_interval_ms_mean",
          "height_work_ms_p50", "rounds_over_zero", "daemon_compiles_in_window",
          "pool_miss_lane_share", "table_builds_per_s", "apply_app_ms_p50",
          "p2p_io_frames_per_wake", "tx_edge_ms_p50", "tx_gate_ms_p50",
          "tx_to_proposer_ms_p50", "tx_await_reap_ms_p50",
          "tx_reap_to_commit_ms_p50", "tx_commit_to_reply_ms_p50",
          "vote_batch_width_mean", "daemon_decode_ms_p50", "daemon_marshal_ms_p50",
          "daemon_dispatch_ms_p50", "daemon_device_wait_ms_p50",
          "daemon_reply_ms_p50", "daemon_calls_in_flight_mean",
          "daemon_verdict_lag_ms_p50", "node_verify_wait_ms_per_height_p50",
          "node_verify_ipc_ms_per_height_p50", "height_propose_ms_p50",
          "height_votes_ms_p50", "height_commit_tail_ms_p50", "pool_evictions_per_s",
          "pool_resident_share", "table_build_ms_p50", "pool_update_ms_p50",
          "build_kernel_roofline_share", "update_kernel_roofline_share",
          "ladder_kernel_roofline_share", "apply_verify_ms_p50"}
OWN_READER_ON_A = {"device_idle_share", "verify_kernel_sigs_per_s",
                   "verify_kernel_roofline_share"}
# the parts' arrival counts blocks of two parts or more: at this cell's 38
# updates a second a block is one part, and the reader finds nothing
MANY_PART_BLOCKS = {"block_parts_ms_p50"}
SMALL = {"config": {"recordcount": 1024, "load": {"txs_per_block": 64},
                    "pool_history": {"lanes_a_batch": 16},
                    "daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                       "TENDERMINT_DEVD_WARM": "",
                                       "TENDERMINT_TPU_COMB_MIN_SIGHT": "2",
                                       "TENDERMINT_TPU_COMB_CAP": "32",
                                       "TENDERMINT_TPU_COMB_OPEN": "1"},
                               "warm_buckets": [8, 16, 32, 64], "warm_passes": 2}},
         "traffic": {"rate_per_s": 40, "readback_sample": 20,
                     "proofs_checked_min": 100}}


def load(path):
    with open(path) as f:
        return json.load(f)


def test_the_cell_its_configuration_and_its_traffic():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    [cell] = [w for w in b["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ycsb-b-signedkv", "ycsb-b", 1)
    [entry] = [c for c in b["configs"] if c["name"] == "ycsb-b-signedkv"]
    assert entry["reduced"] == ["recordcount"] and len(entry["source"]) <= 200
    assert "workloadb" in entry["source"]
    cfg = load(os.path.join(ROOT, entry["file"]))
    assert cfg["deployment"] == "ycsb_verified_net" and "verified_read" in cfg["guarantees"]
    mix = load(os.path.join(BENCH, "traffic", "ycsb-b.json"))
    a = load(os.path.join(BENCH, "traffic", "ycsb-a.json"))
    for key in ("kind", "arrivals", "targets", "request_distribution",
                "zipfian_constant", "update_method", "read_method", "lead_in_s",
                "request_timeout_s", "readback_sample", "forged_writes",
                "forged_kinds", "trace_window_s"):
        assert mix[key] == a[key], key
    assert (mix["read_share"], mix["update_share"]) == (0.95, 0.05)
    assert mix["proof_sample"] >= mix["proofs_checked_min"] >= 1000
    rungs = (240, 480, 960, 1920, 3840)
    knee = mix["sweep"]["knee_ops_per_s"]
    # four fifths of the knee, or of a rung below it where the knee's rate
    # failed the noise gate
    rung = mix["sweep"].get("rung_ops_per_s", knee)
    assert knee in rungs and rung in rungs and rung <= knee
    assert mix["rate_per_s"] == 0.8 * rung
    assert str(knee) in mix["sweep"]["readings"]
    assert str(rung) in mix["sweep"]["readings"]
    ends = {m["name"]: m for m in b["end_to_end"]}
    for name in ("commit_latency_p50_ms", "commit_latency_p95_ms",
                 "read_latency_p50_ms", "setup_s"):
        assert CELL in ends[name].get("workloads", [CELL]), name


def test_every_metric_of_the_cell_has_its_entry_its_file_and_its_reader():
    import run as bench_run

    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"]: m for m in b["per_layer"] if CELL in m["workloads"]}
    assert set(mine) == SHARED | set(READ_METRICS)
    of_a = {m["name"] for m in b["per_layer"] if "ycsb-a.steady" in m["workloads"]}
    assert of_a - set(mine) == OWN_READER_ON_A | MANY_PART_BLOCKS
    for name in OWN_READER_ON_A:
        spec = load(os.path.join(BENCH, "metrics", name + ".json"))
        assert "ycsb-a.steady" in spec["by_workload"]
    for name, m in mine.items():
        spec = load(os.path.join(BENCH, "metrics", name + ".json"))
        reader, params = bench_run.metric_reader(spec, CELL)
        assert hasattr(importlib.import_module("readers." + reader), "read")
        if name in SHARED:
            # read as ycsb-a.steady reads it
            assert "ycsb-a.steady" in m["workloads"]
            assert bench_run.metric_reader(spec, "ycsb-a.steady") == (reader, params)
    for name, (piece, q) in READ_METRICS.items():
        m = mine[name]
        assert m["workloads"] == ["ycsb-a.steady", CELL]
        assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
            "entry: rpc + app query", "read_latency_p50_ms", "program_span", "ms")
        spec = load(os.path.join(BENCH, "metrics", name + ".json"))
        assert {k: spec[k] for k in m} == m
        assert (spec["reader"], spec["params"]) == ("read_road_percentile",
                                                    {"piece": piece, "q": q})


def test_the_read_road_reader_on_a_synthetic_run(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_read_road import synthetic_run

    from harness.observe import quantile

    road = importlib.import_module("readers.read_road_percentile")
    obs, want = synthetic_run(tmp_path)
    for piece in road.PIECES:
        for q in (50, 95):
            got = road.read(obs, {"piece": piece, "q": q}, {})
            assert got == pytest.approx(quantile(
                [want[i][0][piece] for i in sorted(want)], q / 100), abs=1e-3)
    # a program from before the read traces: its dumps carry none
    obs2, _ = synthetic_run(tmp_path / "old")
    run = os.path.dirname(obs2.trace["dir"])
    for d in os.listdir(run):
        if d.startswith("node"):
            [f] = os.listdir(os.path.join(run, d, "flightrec"))
            path = os.path.join(run, d, "flightrec", f)
            dump = load(path)
            del dump["query_traces"]
            with open(path, "w") as fh:
                json.dump(dump, fh)
    assert road.read(obs2, {"piece": "lock", "q": 50}, {}) is None
    # too few joined reads
    obs3, want3 = synthetic_run(tmp_path / "few", n_ops=100)
    assert len(want3) < road.MIN_JOINED
    assert road.read(obs3, {"piece": "lock", "q": 50}, {}) is None



def _proof_record(monkeypatch, n_reads=60):
    """A generator record of `n_reads` sampled reads over four nodes,
    each answered at height 1 or 2 of a real treap of YCSB records with
    its value and proof, and the nodes' `commit` RPC: header h + 1
    carries the root after block h."""
    import hashlib
    import random

    from reference import ycsb_ref
    from scenarios import ycsb_verified_net

    from tendermint_tpu.statetree import VersionedTree

    rng = random.Random(43)
    tree, roots = VersionedTree(), {}
    values = {1: {r: rng.randbytes(100) for r in range(200)}}
    values[2] = {**values[1], **{r: rng.randbytes(100) for r in range(0, 200, 3)}}
    for h in (1, 2):
        for r, v in values[h].items():
            tree.set(ycsb_ref.key_of(r), v)
        roots[h] = tree.commit(h)
    lg = {k: [] for k in ("ok", "height", "node", "record", "got")}
    lg["proofs"] = {}
    for i in range(n_reads):
        r, h = rng.randrange(200), 1 + i % 2
        v = values[h][r]
        proof = tree.prove(ycsb_ref.key_of(r), h)
        lg["proofs"][str(i)] = [v.hex(), json.dumps(proof.to_json(), sort_keys=True).encode().hex()]
        lg["ok"].append(True)
        lg["height"].append(h)
        lg["node"].append(i % 4)
        lg["record"].append(r)
        lg["got"].append([len(v), hashlib.sha256(v).hexdigest()])
    lg["proof_sample"] = list(range(n_reads))
    asked = []

    def call(addr, method, params, timeout=10.0):
        assert method == "commit"
        asked.append((addr, params["height"]))
        return {"header": {"app_hash": roots[params["height"] - 1].hex()}}

    monkeypatch.setattr(ycsb_verified_net.rpc, "call", call)
    monkeypatch.setattr(ycsb_verified_net.rpc, "wait_heights", lambda *a: True)
    return lg, asked


def _judged(lg, minimum=50):
    from scenarios import ycsb_verified_net

    addrs = [("127.0.0.1", 40000 + k) for k in range(4)]
    comparisons, notes = ycsb_verified_net.judge_proofs(
        {"proofs_checked_min": minimum}, addrs, lg)
    got = {name: (value, limit) for name, value, limit in comparisons}
    # `run.py`: a run is correct when every compared value is within its limit
    return got, notes, all(v <= lim for v, lim in got.values())


def test_the_proof_judge_passes_honest_reads(monkeypatch):
    lg, asked = _proof_record(monkeypatch)
    got, notes, correct = _judged(lg)
    assert correct and got == {"proofs_not_verifying": (0, 0),
                               "proofs_checked_under_minimum": (0, 0)}
    assert notes["proofs_checked"] == 60
    # header (named height + 1), from the node that answered, once a pair
    want = {(("127.0.0.1", 40000 + lg["node"][i]), lg["height"][i] + 1)
            for i in range(60)}
    assert sorted(asked) == sorted(want) and {h for _, h in asked} == {2, 3}


@pytest.mark.parametrize("fault", ["proof_byte", "height_named_one_low",
                                   "value_not_the_answers", "too_few_checked"])
def test_the_proof_judge_counts_a_planted_fault(monkeypatch, fault):
    lg, _ = _proof_record(monkeypatch)
    name, want = "proofs_not_verifying", 1
    if fault == "proof_byte":
        # one hex digit of one kept proof changed
        value_hex, proof_hex = lg["proofs"]["7"]
        raw = bytearray(bytes.fromhex(proof_hex))
        at = next(k for k in range(len(raw) // 2, len(raw))
                  if chr(raw[k]) in "0123456789ABCDEF")
        raw[at] = ord("0") if raw[at] != ord("0") else ord("1")
        lg["proofs"]["7"] = [value_hex, bytes(raw).hex()]
    elif fault == "height_named_one_low":
        # a read answered at height 2 that names height 1: its proof is
        # then held to header 2, whose app hash is the root after block 1
        i = next(i for i in range(60) if lg["height"][i] == 2)
        lg["height"][i] = 1
    elif fault == "value_not_the_answers":
        # the proof and value kept are not the answer the generator timed
        lg["got"][11] = [100, "00" * 32]
    else:
        lg["proof_sample"] = lg["proof_sample"] + [60]
        lg["ok"].append(False)
        name, want = "proofs_checked_under_minimum", 11
        got, _, correct = _judged(lg, minimum=71)
        assert got[name] == (want, 0) and not correct
        return
    got, _, correct = _judged(lg)
    assert got[name] == (want, 0) and not correct

def test_rehearsal_run_is_correct():
    import run as bench_run

    argv = ["--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "10",
            "--trace", "1", "--rehearsal", "--scale", json.dumps(SMALL)]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert line["correct"] is True and not over, over
    assert line["attempted"] == 400 and line["failed"] == 0
    assert {"proofs_not_verifying", "proofs_checked_under_minimum"} <= set(line["compared"])
    assert line["notes"]["proofs_checked"] >= 100
    assert set(READ_METRICS) <= set(line["metrics"])
    assert {"commit_latency_p50_ms", "read_latency_p50_ms", "setup_s"} <= set(
        line["end_to_end_of_this_traced_run"])
