"""A read's road (libs/txtrace.py read traces) and the YCSB B deployment
around it: the four stamps of a sampled `abci_query`, sampling by the
node's count of reads, the stop dump and the histogram; the benchmark's
reader of the four pieces (`perfbench/readers/read_road_percentile.py`),
which telescope to each read's own latency; the plain proof check
(`perfbench/reference/statetree_ref.py`) against the program's proofs;
the configuration `ycsb-b-signedkv` against `ycsb-a-signedkv`; and the
read path of a live `signedkv` node, through its RPC, against
`perfbench/reference/ycsb_ref.py` and `statetree_ref`."""

from __future__ import annotations

import glob
import http.client
import json
import os
import random
import sys
import threading
import time

import pytest

from tendermint_tpu.libs import telemetry, txtrace
from tendermint_tpu.libs.txtrace import READ_STAGES, TxTraceRecorder, stamp_read
from tendermint_tpu.node.flightrec import FlightRecorder
from tendermint_tpu.statetree import VersionedTree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import statetree_ref, ycsb_ref  # noqa: E402


def _serve(rec, rid, at, stamps=(("app_lock", 0.002), ("proved", 0.005)),
           reply=0.007):
    """One read as the RPC handler's thread serves it."""
    tr = rec.read_ingress(rid, at=at)
    if tr is not None:
        for stage, dt in stamps:
            stamp_read(stage)
            tr.stamps[stage] = at + dt      # a known instant for the test
        rec.read_reply(tr, at=at + reply)
    return tr


# -- the trace ------------------------------------------------------------------


def test_reads_are_sampled_by_the_nodes_count_not_by_their_id():
    rec = TxTraceRecorder(sample_n=4)
    # the same id every time (a hot record's read would be): the count
    # still samples 1 in 4
    got = [_serve(rec, 7, at=float(k)) is not None for k in range(12)]
    assert got == [True, False, False, False] * 3
    assert [t["stages"]["rpc_ingress"] for t in rec.read_dump()] == [0.0, 4.0, 8.0]


def test_off_by_n_zero_and_by_the_kill_switch(monkeypatch):
    assert _serve(TxTraceRecorder(sample_n=0), 1, at=0.0) is None
    monkeypatch.setenv("TENDERMINT_TXTRACE_DISABLE", "1")
    rec = TxTraceRecorder(sample_n=1)
    assert _serve(rec, 1, at=0.0) is None and rec.read_dump() == []


def test_a_reads_stamps_are_its_own_threads_and_keep_the_first():
    rec = TxTraceRecorder(sample_n=1)
    tr = rec.read_ingress("a", at=time.time())
    stamp_read("app_lock")
    first = tr.stamps["app_lock"]
    stamp_read("app_lock")                        # keep-first
    other = threading.Thread(target=stamp_read, args=("proved",))
    other.start()
    other.join()
    assert tr.stamps["app_lock"] == first and "proved" not in tr.stamps
    stamp_read("proved")
    rec.read_reply(tr)
    assert list(tr.stamps) == list(READ_STAGES)
    stamp_read("proved")                          # served: nothing to stamp
    assert tr.stamps["proved"] <= tr.stamps["rpc_reply"]
    [dumped] = rec.read_dump()
    assert dumped["id"] == "a" and set(dumped["stages"]) == set(READ_STAGES)
    # the spans telescope to ingress -> reply
    st = dumped["stages"]
    assert sum(dumped["spans"].values()) == pytest.approx(
        st["rpc_reply"] - st["rpc_ingress"], abs=1e-5)


def test_the_histogram_and_the_ring():
    reg = telemetry.Registry()
    rec = TxTraceRecorder(sample_n=1, ring=2)
    rec.metrics_registry = reg
    for k in range(10):
        _serve(rec, k, at=float(k))
    # four times the tx ring
    assert [t["id"] for t in rec.read_dump()] == [2, 3, 4, 5, 6, 7, 8, 9]
    text = reg.render_prometheus()
    for stage in ("app_lock", "proved", "rpc_reply"):
        assert f'query_stage_seconds_count{{stage="{stage}"}} 10' in text, stage
    assert txtrace.txtrace_hists(reg)["query"] is txtrace.txtrace_hists(reg)["query"]


def test_the_stop_dump_carries_the_read_traces(tmp_path):
    rec = TxTraceRecorder(sample_n=2)
    for k in range(4):
        _serve(rec, k, at=float(k))
    fr = FlightRecorder(home=str(tmp_path), ring=8)
    with open(fr.dump("bare")) as f:
        assert json.load(f)["query_traces"] == []
    fr.query_traces_fn = rec.read_dump
    with open(fr.dump("stop")) as f:
        got = json.load(f)["query_traces"]
    assert [t["id"] for t in got] == [0, 2]
    assert all(set(t["stages"]) == set(READ_STAGES) for t in got)


# -- the benchmark's reader of the four pieces -------------------------------------


def synthetic_run(tmp_path, n_ops=400, lead=20, seed=3, nodes=4):
    """A run's directory as a traced YCSB run leaves it: operation i went
    to node i % nodes with JSON-RPC id i; every node traced 1 in 4 of the
    reads it answered. Returns (obs, {op: (its four pieces, done - sent)
    in ms})."""
    from harness.observe import Observations

    rng = random.Random(seed)
    run = tmp_path / "run"
    (run / "trace").mkdir(parents=True)
    lg = {"kind": [], "ok": [], "node": [], "sent": [], "done": [],
          "lead_in_operations": lead}
    dumps = {k: [] for k in range(nodes)}
    count = {k: 0 for k in range(nodes)}
    want = {}
    for i in range(lead + n_ops):
        kind = "update" if rng.random() < 0.05 else "read"
        node = i % nodes
        sent = 50.0 + 0.01 * i                    # the generator's clock
        t0 = 1_700_000_000.0 + 0.01 * i + 0.0004  # the node's wall clock
        lock, prove, reply = (rng.uniform(0, 0.2), rng.uniform(0.001, 0.003),
                              rng.uniform(0.0005, 0.002))
        edge = rng.uniform(0.0005, 0.003)
        done = sent + edge + lock + prove + reply
        lg["kind"].append(kind)
        lg["ok"].append(i % 37 != 5)
        lg["node"].append(node)
        lg["sent"].append(sent)
        lg["done"].append(done)
        if kind != "read":
            continue
        count[node] += 1
        if count[node] % 4 != 1:
            continue
        dumps[node].append({"id": i, "stages": {
            "rpc_ingress": t0, "app_lock": t0 + lock,
            "proved": t0 + lock + prove, "rpc_reply": t0 + lock + prove + reply}})
        if i >= lead and lg["ok"][i]:
            want[i] = ({"edge": 1e3 * edge, "lock": 1e3 * lock,
                        "prove": 1e3 * prove, "reply": 1e3 * reply},
                       1e3 * (done - sent))
    # the harness's own reads after the window: an id of another kind
    dumps[0].append({"id": "perfbench", "stages": {"rpc_ingress": 1.0}})
    for k, traces in dumps.items():
        d = run / f"node{k}" / "flightrec"
        d.mkdir(parents=True)
        (d / "dump-20261019T000000-stop.json").write_text(json.dumps(
            {"reason": "stop", "tx_traces": [], "query_traces": traces}))
    (run / "loadgen.out").write_text(json.dumps(lg))
    obs = Observations(window_s=45.0, open_wall=1_700_000_000.0)
    obs.trace = {"dir": str(run / "trace")}
    return obs, want


def test_the_four_pieces_telescope_to_each_reads_latency(tmp_path):
    from readers import read_road_percentile as reader

    obs, want = synthetic_run(tmp_path)
    roads = reader.roads(obs)
    assert len(roads) == len(want) >= reader.MIN_JOINED
    # (an epoch second as a float is good to some 0.2 us: 1 us of room)
    for road, (pieces, total) in zip(roads, [want[i] for i in sorted(want)]):
        for k, v in pieces.items():
            assert road[k] == pytest.approx(v, abs=1e-3), k
        assert sum(road[k] for k in reader.PIECES) == pytest.approx(total, abs=1e-9)


# -- the plain proof check against the program's proofs ------------------------------


def _proof_hex(proof) -> str:
    # as the app answers it (abci/apps/kvstore.py): the JSON, sorted keys
    return json.dumps(proof.to_json(), sort_keys=True).encode().hex()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_statetree_ref_accepts_the_programs_proofs(seed):
    rng = random.Random(seed)
    entries = {rng.randbytes(rng.randint(1, 24)): rng.randbytes(rng.randint(0, 64))
               for _ in range(300)}
    tree = VersionedTree()
    for k, v in entries.items():
        tree.set(k, v)
    root = tree.commit(1)
    for k in rng.sample(sorted(entries), 40):
        assert statetree_ref.proves(_proof_hex(tree.prove(k, 1)), k,
                                    entries[k], root)
    absent = [k for k in (rng.randbytes(rng.randint(1, 24)) for _ in range(40))
              if k not in entries]
    for k in absent:
        assert statetree_ref.proves(_proof_hex(tree.prove(k, 1)), k, b"", root)
    # a second version: the first's proofs do not verify against it
    k = next(iter(entries))
    tree.set(k, b"changed")
    root2 = tree.commit(2)
    assert not statetree_ref.proves(_proof_hex(tree.prove(k, 1)), k, entries[k], root2)
    assert statetree_ref.proves(_proof_hex(tree.prove(k, 2)), k, b"changed", root2)
    empty = VersionedTree()
    assert statetree_ref.proves(_proof_hex(empty.prove(b"x")), b"x", b"", b"")


def test_statetree_ref_refuses_a_proof_with_one_byte_changed():
    rng = random.Random(9)
    entries = {b"user%d" % rng.getrandbits(60): rng.randbytes(100) for _ in range(200)}
    tree = VersionedTree()
    for k, v in entries.items():
        tree.set(k, v)
    root = tree.commit(1)
    key = sorted(entries)[17]
    good = bytes.fromhex(_proof_hex(tree.prove(key, 1)))
    assert statetree_ref.proves(good.hex(), key, entries[key], root)
    hexdigits = [i for i, c in enumerate(good) if chr(c) in "0123456789ABCDEF"]
    for i in rng.sample(hexdigits, 60):
        bad = bytearray(good)
        bad[i] = ord("0") if bad[i] != ord("0") else ord("1")
        assert not statetree_ref.proves(bytes(bad).hex(), key, entries[key], root)
    # the value, the key or the root changed
    assert not statetree_ref.proves(good.hex(), key, entries[key][:-1] + b"!", root)
    assert not statetree_ref.proves(good.hex(), key + b"0", entries[key], root)
    assert not statetree_ref.proves(good.hex(), key, entries[key], root[:-1] + b"\0")
    assert not statetree_ref.proves("not hex", key, entries[key], root)
    # an absence proof shown for a key that is there
    assert not statetree_ref.proves(good.hex(), key, b"", root)


def test_the_apps_hash_is_the_root_statetree_ref_checks():
    from tendermint_tpu.abci.apps.signedkv import SignedKVStoreApp, make_sig_tx

    app = SignedKVStoreApp()
    payloads = [b"k%d=v%d" % (i, i) for i in range(30)]
    res = app.deliver_txs([make_sig_tx(bytes([i]) * 32, p)
                           for i, p in enumerate(payloads)])
    assert all(r.code == 0 for r in res)
    app_hash = app.commit().data
    for key in (b"k3", b"k29", b"missing"):
        q = app.query(key, prove=True)
        assert q.height == 1
        assert statetree_ref.proves(q.proof.hex(), key, q.value or b"", app_hash)


# -- the configuration ------------------------------------------------------------


def test_ycsb_b_is_ycsb_a_outside_its_named_keys():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            return json.load(f)

    a, b = load("ycsb-a-signedkv"), load("ycsb-b-signedkv")
    own = {"name", "source", "why", "deployment"}
    assert set(a) == set(b)
    for k in set(a) - own - {"guarantees", "assumed"}:
        assert a[k] == b[k], k
    assert {k: v for k, v in b["guarantees"].items() if k != "verified_read"} \
        == a["guarantees"]
    assert {k: v for k, v in b["assumed"].items() if k != "read_trace"} == a["assumed"]
    assert "verified_read" in b["guarantees"] and "read_trace" in b["assumed"]
    assert (a["deployment"], b["deployment"]) == ("ycsb_net", "ycsb_verified_net")
    assert b["reduced"] == ["recordcount"] and b["recordcount"] == 65536


# -- the read path of a live node --------------------------------------------------


def _post(port: int, method: str, params: dict, rid):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", "/", body=json.dumps(
            {"jsonrpc": "2.0", "id": rid, "method": method, "params": params}),
            headers={"Content-Type": "application/json",
                     "Connection": "close"})
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def test_the_read_path_of_a_live_node_against_the_references(tmp_path):
    """A seeded load of YCSB records written through a signedkv node's
    RPC, then every record read back with a proof: each value is the one
    `ycsb_ref` says the record holds, each proof verifies by
    `statetree_ref` against header (height + 1)'s app hash, and the
    node's stop dump holds 1 in 4 of the reads, each with its four
    stamps in order."""
    from harness import ycsb

    from tendermint_tpu.config import reset_test_root
    from tendermint_tpu.node import default_new_node

    seed, records = 2**31 + 5, list(range(16))
    cfg = reset_test_root(str(tmp_path))
    cfg.base.proxy_app = "signedkv"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    node = default_new_node(cfg)
    node.start()
    keypair = ycsb.make_keypair()
    try:
        port = node.rpc_port()
        for r in records:
            payload = ycsb_ref.key_of(r) + b"=" + ycsb_ref.value_of(seed, r, 0)
            pub, sign = keypair(ycsb.record_secret(seed, r))
            res = _post(port, "broadcast_tx_sync",
                        {"tx": (pub + sign(payload) + payload).hex()}, "w")
            assert res["result"]["code"] == 0, res
        deadline = time.time() + 60
        while node.mempool.size() or node.block_store.height() < 2:
            assert time.time() < deadline, "the load did not commit"
            time.sleep(0.05)
        answers = {}
        for i, r in enumerate(records * 2 + [None]):
            key = ycsb_ref.key_of(r) if r is not None else b"user-absent"
            res = _post(port, "abci_query", {"data": key.hex(), "prove": True}, i)
            assert res["id"] == i and not res["error"], res
            answers[i] = (key, r, res["result"]["response"])
        top = max(a[2]["height"] for a in answers.values())
        while node.block_store.height() < top + 1:
            assert time.time() < deadline, "no header above the reads"
            time.sleep(0.05)
        for key, r, resp in answers.values():
            value = bytes.fromhex(resp["value"])
            assert value == (ycsb_ref.value_of(seed, r, 0) if r is not None else b"")
            header = _post(port, "commit", {"height": resp["height"] + 1},
                           "h")["result"]["header"]
            assert statetree_ref.proves(resp["proof"], key, value,
                                        bytes.fromhex(header["app_hash"]))
    finally:
        node.stop()
    [dump] = glob.glob(str(tmp_path / "flightrec" / "dump-*-stop*.json"))
    with open(dump) as f:
        traces = [t for t in json.load(f)["query_traces"]
                  if isinstance(t["id"], int)]
    assert [t["id"] for t in traces] == [i for i in answers if i % 4 == 0]
    for t in traces:
        st = t["stages"]
        assert st["rpc_ingress"] <= st["app_lock"] <= st["proved"] <= st["rpc_reply"]
