"""The reader of a write's road (PR 37, `readers/tx_road_percentile.py`)
on a synthetic run: two nodes' stop dumps with their `tx_traces` and the
generator's result, where every write's instants are known."""

import importlib
import json
import os
import random

import pytest
from conftest import BENCH

from harness.observe import Observations, quantile
from tendermint_tpu.types.tx import tx_hash

road = importlib.import_module("readers.tx_road_percentile")


def _run(tmp_path, n_writes: int, lead: int = 3, ycsb: bool = False,
         with_traces: bool = True, seed: int = 5):
    """A run's directory as a traced run leaves it: node0 and node1, each
    write sent to node i % 2, the block of each proposed by node
    (i // 3) % 2; returns (obs, {write index: its six pieces in ms})."""
    rng = random.Random(seed)
    run = tmp_path / "run"
    (run / "trace").mkdir(parents=True)
    dumps = {0: [], 1: []}
    lg = {"tx": [], "sent": [], "done": [], "ok": [], "node": [],
          "kind": [], ("lead_in_operations" if ycsb else "lead_in_writes"): lead}
    want = {}
    for i in range(lead + n_writes):
        kind = "update" if not ycsb or i % 3 else "read"
        tx = rng.randbytes(96) + b"k%d=v" % i if kind == "update" else b""
        a, p = i % 2, (i // 3) % 2
        t0 = 1_700_000_000.0 + i            # the nodes' wall clock
        sent = 100.0 + i - 0.003            # the generator's own clock
        st_a = {"rpc_ingress": t0, "gate_dispatch": t0 + 0.002,
                "sig_gate": t0 + 0.010, "mempool_admit": t0 + 0.012,
                "block_commit": t0 + 0.700, "rpc_reply": t0 + 0.740}
        admit_p = t0 + (0.012 if a == p else 0.030)
        reap_p = t0 + 0.500
        if a == p:
            st_a["reap"] = reap_p
        done = sent + 0.003 + 0.740 + 0.004
        lg["tx"].append(tx.hex())
        lg["sent"].append(sent)
        lg["done"].append(done)
        lg["ok"].append(True)
        lg["node"].append(a)
        lg["kind"].append(kind)
        if kind != "update":
            continue
        h = tx_hash(tx).hex().upper()
        dumps[a].append({"hash": h, "source": "rpc", "stages": st_a})
        if a != p:
            dumps[p].append({"hash": h, "source": "peer", "stages": {
                "rpc_ingress": t0 + 0.020, "sig_gate": t0 + 0.028,
                "mempool_admit": admit_p, "reap": reap_p,
                "block_commit": t0 + 0.699}})
        if i >= lead:
            want[i] = {"edge": 1000 * (done - sent - 0.740),
                       "gate": 10.0, "to_proposer": 1000 * (admit_p - t0 - 0.010),
                       "await_reap": 1000 * (reap_p - admit_p),
                       "reap_to_commit": 200.0, "commit_to_reply": 40.0}
    if not ycsb:
        del lg["kind"]
    for k, traces in dumps.items():
        d = run / f"node{k}" / "flightrec"
        d.mkdir(parents=True)
        payload = {"reason": "stop", "consensus_traces": []}
        if with_traces:
            payload["tx_traces"] = traces
        (d / "dump-20261015T000000-stop.json").write_text(json.dumps(payload))
    (run / "loadgen.out").write_text(json.dumps(lg))
    obs = Observations(window_s=45.0, open_wall=1_700_000_000.0)
    obs.trace = {"dir": str(run / "trace")}
    return obs, want


def _read(obs, piece):
    return road.read(obs, {"piece": piece, "q": 50}, {})


@pytest.mark.parametrize("ycsb", [False, True])
def test_six_pieces_partition_each_writes_sent_to_done(tmp_path, ycsb):
    obs, want = _run(tmp_path, 120, ycsb=ycsb)
    assert _read(obs, "gate") == pytest.approx(10.0)
    roads = obs.trace["tx_roads"]
    assert len(roads) == len(want)    # a YCSB cell's reads never join
    for r, (i, w) in zip(roads, sorted(want.items())):
        assert sum(r[p] for p in road.PIECES) == pytest.approx(
            r["total"], abs=1e-4)
        for p in road.PIECES:
            assert r[p] == pytest.approx(w[p], abs=1e-4), (i, p)
    for p in road.PIECES:
        assert _read(obs, p) == pytest.approx(
            quantile([w[p] for w in want.values()], 0.5), abs=1e-4)


def test_nothing_under_fifty_joined_writes(tmp_path):
    obs, want = _run(tmp_path, 49)
    assert len(want) == 49
    assert _read(obs, "edge") is None
    assert len(obs.trace["tx_roads"]) == 49


def test_nothing_from_a_program_without_tx_traces(tmp_path):
    """The parent's nodes dump no `tx_traces`: no metric, no raise."""
    obs, _ = _run(tmp_path, 80, with_traces=False)
    assert all(_read(obs, p) is None for p in road.PIECES)


def test_every_piece_has_a_metric_in_every_cell():
    entries = {m["name"]: m for m in json.load(
        open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))["per_layer"]}
    for p in road.PIECES:
        name = f"tx_{p}_ms_p50"
        assert entries[name]["workloads"] == ["net4.steady", "committee.steady",
                                              "committee-wan.steady", "ycsb-a.steady"]
        spec = json.load(open(os.path.join(BENCH, "metrics", name + ".json")))
        assert spec["reader"] == "tx_road_percentile" and "by_workload" not in spec
        assert spec["params"] == {"piece": p, "q": 50}
