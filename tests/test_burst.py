"""A burst of signed writes (the benchmark's `burst.drain`): the signature
gate's counters, the height stamps of a block's parts and of its apply on
a live node, and the benchmark's plain reference of the burst
(`perfbench/reference/burst_ref.py`) and configuration
(`perfbench/configs/burst-signedkv.json`)."""

from __future__ import annotations

import glob
import json
import os
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def wait_until(cond, timeout=30.0, tick=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


# -- the signature gate --------------------------------------------------------


class _HeldVerifier:
    """verify_batch_async that blocks until released: the gate's drain
    thread sits in dispatch and its backlog fills."""

    def __init__(self):
        self.release = threading.Event()

    def verify_batch_async(self, items):
        self.release.wait(10)
        return lambda: [True] * len(items)


def test_the_gate_counts_its_batches_and_a_full_backlog_refuses():
    from tendermint_tpu.mempool.mempool import SigBatcher

    v = _HeldVerifier()
    got = []
    gate = SigBatcher(v, parse=lambda tx: tx, max_batch=4, max_backlog=3,
                      on_results=got.extend)
    try:
        assert gate.submit(b"a", 0)
        # the drain thread took the first write and waits in dispatch
        assert wait_until(lambda: gate.batches == 1, timeout=5)
        assert [gate.submit(b"x%d" % k, k + 1) for k in range(4)] == [
            True, True, True, False]
        assert (gate.dropped, gate.batches, gate.lanes) == (1, 1, 1)
        v.release.set()
        assert wait_until(lambda: len(got) == 4, timeout=5)
        assert (gate.batches, gate.lanes, gate.dropped) == (2, 4, 1)
        assert gate.delivered == 4
    finally:
        v.release.set()
        gate.stop()


def test_a_live_nodes_gate_counters_and_block_stamps(tmp_path):
    """One signedkv validator: a batch of signed writes rides the gate
    and one block; /debug/queues and the stop dump's counters carry the
    gate's batches, lanes and refusals; the block's height trace carries
    its txs, its parts and `parts_complete`; the apply of a block with
    writes notes `apply_verify_s` and `apply_app_s`."""
    import urllib.request

    from tendermint_tpu.abci.apps.signedkv import make_sig_tx
    from tendermint_tpu.config import reset_test_root
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.rpc.client import HTTPClient

    cfg = reset_test_root(str(tmp_path))
    cfg.base.proxy_app = "signedkv"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    n = default_new_node(cfg)
    n.start()
    try:
        assert wait_until(lambda: n.block_store.height() >= 1, timeout=30)
        cli = HTTPClient(f"127.0.0.1:{n.rpc_port()}")
        txs = [make_sig_tx(bytes([7, k]) + b"\x07" * 30, b"burst-%d=v%d" % (k, k))
               for k in range(12)]
        for tx in txs[:-1]:
            cli.broadcast_tx_async(tx=tx.hex())
        res = cli.broadcast_tx_commit(tx=txs[-1].hex())
        assert res["deliver_tx"]["code"] == 0
        h = res["height"]
        # the apply of the last block with writes is noted on the next
        # height's trace (pipelined): let it seal
        assert wait_until(lambda: n.block_store.height() >= h + 2, timeout=30)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{n.rpc_port()}/debug/queues", timeout=10) as r:
            mp = json.loads(r.read().decode())["mempool"]
    finally:
        n.stop()
    assert mp["sig_gate_lanes"] >= 12 and 1 <= mp["sig_gate_batches"] <= 12
    assert mp["sig_gate_dropped"] == 0
    assert (mp["ingest_backlog"], mp["ingest_dropped"]) == (0, 0)
    [dump] = glob.glob(str(tmp_path / "flightrec" / "dump-*-stop*.json"))
    with open(dump) as f:
        d = json.load(f)
    c = d["counters"]
    assert c["sig_gate_lanes"] >= 12 and c["sig_gate_dropped"] == 0
    assert c["mempool_ingest_dropped"] == 0
    assert 1 <= c["sig_gate_batches"] <= c["sig_gate_lanes"]
    traces = {t["height"]: t for t in d["consensus_traces"]}
    written = [t for t in traces.values() if t["aux"].get("txs", 0) > 0]
    assert sum(t["aux"]["txs"] for t in written) == 12
    for t in traces.values():
        assert t["aux"]["parts"] >= 1
        arr = t["arrivals"]
        assert arr["proposal"] <= arr["parts_complete"] <= arr["commit"]
    # the apply of each block with writes, on the height it overlapped
    for t in written:
        nxt = traces.get(t["height"] + 1)
        if nxt is None:
            continue
        aux = nxt["aux"]
        assert aux["apply_app_s"] > 0
        if t["aux"]["txs"] >= 2:     # the whole-block signature call
            assert aux["apply_verify_s"] > 0
        assert aux.get("apply_verify_s", 0) + aux["apply_app_s"] \
            <= aux["overlap_apply_s"] + 1e-6
    # a block without writes notes no apply
    empty = [t for t in traces.values() if not t["aux"].get("txs")
             and t["height"] + 1 in traces and t["height"] > h]
    assert empty and all("apply_app_s" not in traces[t["height"] + 1]["aux"]
                         for t in empty)


def test_apply_stamps_only_inside_an_apply_clock():
    from tendermint_tpu.consensus import trace as ctrace
    from tendermint_tpu.libs import applyclock

    applyclock.stamp("apply_verify")            # no apply here: nothing
    with applyclock.clock() as stamps:
        applyclock.stamp("apply_verify")
        first = stamps["apply_verify"]
        applyclock.stamp("apply_verify")        # the first stamp wins
        applyclock.stamp("apply_app")
    assert stamps["apply_verify"] == first
    notes = ctrace.apply_notes(stamps)
    assert set(notes) == {"apply_verify_s", "apply_app_s"}
    assert notes["apply_verify_s"] >= 0 and notes["apply_app_s"] >= 0
    assert ctrace.apply_notes({"start": 1.0, "apply_app": 3.5}) == {
        "apply_app_s": 2.5}


# -- the benchmark's reference and configuration --------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 99])
def test_the_burst_reference_is_the_seeds_and_the_generators(seed):
    from harness import burst_loadgen
    from reference import burst_ref

    a, b = burst_ref.Burst(seed, 200, 200), burst_ref.Burst(seed, 200, 200)
    assert a.forged == b.forged and len(a.forged) == 12
    assert sorted(a.forged.values()).count("signature") == 6
    assert burst_ref.Burst(seed + 1, 200, 200).forged != a.forged
    for p in a.forged:              # a valid write on either side
        assert p - 1 not in a.forged and p + 1 not in a.forged
    writes, forged = burst_loadgen.plan(seed, 200, 200, REPO)
    assert forged == sorted(a.forged)
    assert all(a.tx_fits(j, w["tx"]) for j, w in enumerate(writes))
    for j in list(a.forged)[:4] + a.valid()[:2]:
        assert a.verdict(writes[j]["tx"]) == (j not in a.forged)
    values = a.values()
    j = a.valid()[0]
    assert values.get(b"b%d-%d" % (seed % 1000003, j)) == b"v%d" % j


def test_burst_configuration_is_net4s_outside_the_keys_it_names():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            return json.load(f)

    burst, net4 = load("burst-signedkv"), load("net4-signedkv")
    named = {"name", "deployment", "source", "why", "upstream_limits",
             "burst_writes", "burst_writes_published", "daemon", "assumed",
             "reduced"}
    assert set(burst) - named == set(net4) - named
    for key in set(burst) - named:
        assert burst[key] == net4[key], key
    assert {k: v for k, v in burst["daemon"].items() if k != "warm_buckets"} \
        == {k: v for k, v in net4["daemon"].items() if k != "warm_buckets"}
    assert set(net4["daemon"]["warm_buckets"]) < set(burst["daemon"]["warm_buckets"])
    assert max(burst["daemon"]["warm_buckets"]) == 2048
    # net4-signedkv's assumptions, the two this cell restates aside
    for k, v in net4["assumed"].items():
        if k not in ("signers", "key_value_sizes"):
            assert burst["assumed"][k] == v, k
    for k in ("burst_signed", "signers", "client_connections",
              "key_value_sizes", "warm_buckets"):
        assert burst["assumed"][k], k
    assert burst["upstream_limits"] == {
        "max_block_size_txs": 10000, "block_part_size_bytes": 65536,
        "send_rate": 512000, "recv_rate": 512000, "mempool_cache_size": 100000,
        "rpc_max_connections": 512, "rpc_max_inflight": 256}
    assert burst["burst_writes_published"] == 50000
    assert burst["reduced"] == (["burst_writes"]
                                if burst["burst_writes"] < 50000 else [])
    assert len(burst["source"]) <= 200


# -- the repairs a burst forced ------------------------------------------------


class _Peer:
    def id(self):
        return "peer-a"


class _BlockingMempool:
    """check_tx that blocks until released, as the real one does behind
    its lock while a block commits."""

    def __init__(self):
        self.release = threading.Event()
        self.checked = []

    def check_tx(self, tx, source="rpc", source_id=""):
        self.release.wait(10)
        self.checked.append((tx, source, source_id))


def test_gossiped_txs_are_checked_off_the_receive_thread(monkeypatch):
    """A connection's receive routine hands a gossiped tx over and returns
    at once, whatever the mempool is doing; one ingest thread checks the
    txs in order; past the backlog a tx is dropped and counted."""
    from tendermint_tpu.config import test_config
    from tendermint_tpu.mempool import reactor as mreactor

    monkeypatch.setattr(mreactor, "INGEST_BACKLOG", 3)
    mp = _BlockingMempool()
    r = mreactor.MempoolReactor(test_config().mempool, mp)
    r.start()
    try:
        msgs = [json.dumps({"type": "tx", "tx": (b"t%d" % k).hex()}).encode()
                for k in range(5)]
        t0 = time.monotonic()
        r.receive(mreactor.MEMPOOL_CHANNEL, _Peer(), msgs[0])
        # the ingest thread holds t0 inside check_tx; the backlog fills
        assert wait_until(lambda: not r._ingest, timeout=5)
        for m in msgs[1:]:
            r.receive(mreactor.MEMPOOL_CHANNEL, _Peer(), m)
        assert time.monotonic() - t0 < 2.0
        assert r.ingest_dropped == 1
        mp.release.set()
        assert wait_until(lambda: len(mp.checked) == 4, timeout=5)
        assert [c[0] for c in mp.checked] == [b"t0", b"t1", b"t2", b"t3"]
        assert {c[1:] for c in mp.checked} == {("peer", "peer-a")}
    finally:
        mp.release.set()
        r.stop()


@pytest.mark.parametrize("backend", ["sqlite", "memdb", "filedb"])
def test_the_tx_index_writes_a_block_in_one_batch(tmp_path, monkeypatch, backend):
    """A block's results go to the store in one `set_many` (one sqlite
    transaction), never a `set` a key, and read back by hash and by
    height."""
    from tendermint_tpu.abci.types import ResponseDeliverTx
    from tendermint_tpu.libs import db as dbm
    from tendermint_tpu.state.txindex import Batch, KVTxIndexer
    from tendermint_tpu.types.tx import TxResult, tx_hash

    store = dbm.db_provider("tx_index", backend, str(tmp_path))
    calls = []
    real = type(store).set_many

    def spy(self, pairs):
        pairs = list(pairs)
        calls.append(len(pairs))
        return real(self, pairs)

    monkeypatch.setattr(type(store), "set_many", spy)
    if backend == "sqlite":
        monkeypatch.setattr(dbm.SqliteDB, "set", lambda *a: pytest.fail("set"))
    idx = KVTxIndexer(store)
    batch = Batch()
    txs = [b"tx-%d" % k for k in range(1000)]
    for k, tx in enumerate(txs):
        batch.add(TxResult(height=7, index=k, tx=tx,
                           result=ResponseDeliverTx(code=k % 2)))
    idx.add_batch(batch)
    assert calls == [2000]
    got = idx.get(tx_hash(txs[501]))
    assert (got.height, got.index, got.tx, got.result.code) == (7, 501, txs[501], 1)
    assert idx.prune_to(8) == 1000 and idx.get(tx_hash(txs[0])) is None


def test_a_committed_tx_is_never_admitted_again():
    """A block's txs stay in the mempool's cache after `update`, whether
    or not this node met them before the block: a gossiped copy checked
    after the commit is refused, never proposed a second time."""
    from tendermint_tpu.abci.apps.counter import CounterApp
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.config import test_config
    from tendermint_tpu.mempool.mempool import Mempool, TxInCacheError
    from tendermint_tpu.proxy.app_conn import AppConnMempool

    mp = Mempool(test_config().mempool, AppConnMempool(LocalClient(CounterApp())))
    mp.check_tx(b"seen")
    mp.lock()
    try:
        mp.update(1, [b"seen", b"never-seen"])
    finally:
        mp.unlock()
    assert mp.size() == 0
    for tx in (b"seen", b"never-seen"):
        with pytest.raises(TxInCacheError):
            mp.check_tx(tx)
    mp.check_tx(b"fresh")
    assert mp.size() == 1


@pytest.mark.parametrize("later_blocks", [0, 6])
def test_a_tx_whose_block_commits_during_its_gate_check_is_not_kept(later_blocks):
    """A gossiped copy waits in the signature gate while the block that
    holds it commits, and `later_blocks` more after it: its CheckTx
    answer stands, the pool does not keep it (it would be proposed a
    second time) and the cache does."""
    from tendermint_tpu.abci.apps.signedkv import (
        SignedKVStoreApp, make_sig_tx, parse_sig_tx)
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.config import test_config
    from tendermint_tpu.mempool.mempool import Mempool, SigBatcher, TxInCacheError
    from tendermint_tpu.proxy.app_conn import AppConnMempool

    v = _HeldVerifier()
    gate = SigBatcher(v, parse_sig_tx)
    mp = Mempool(test_config().mempool,
                 AppConnMempool(LocalClient(SignedKVStoreApp(verify_in_app=False))),
                 sig_batcher=gate)
    late, other = (make_sig_tx(bytes([5, k]) + b"\x05" * 30, b"k%d=v" % k)
                   for k in range(2))
    answers = []
    try:
        mp.check_tx(late, cb=answers.append)
        assert wait_until(lambda: gate.batches == 1, timeout=5)
        mp.lock()
        try:
            mp.update(1, [late])
            for h in range(2, 2 + later_blocks):
                mp.update(h, [])
        finally:
            mp.unlock()
        v.release.set()
        assert wait_until(lambda: len(answers) == 1, timeout=5)
        assert answers[0].is_ok and mp.size() == 0
        assert not mp._committed_in_flight
        with pytest.raises(TxInCacheError):
            mp.check_tx(late)
        mp.check_tx(other, cb=answers.append)
        assert wait_until(lambda: len(answers) == 2, timeout=5)
        assert mp.reap(-1) == [other]
    finally:
        v.release.set()
        gate.stop()
