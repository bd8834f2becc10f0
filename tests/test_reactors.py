"""Reactor integration tests: in-process multi-node nets over pipe switches
(reference: consensus/reactor_test.go, mempool/reactor tests,
blockchain/reactor fast-sync behavior)."""

from __future__ import annotations

import tempfile
import threading
import time

import pytest

from tendermint_tpu.abci.apps.counter import CounterApp
from tendermint_tpu.abci.apps.kvstore import KVStoreApp
from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.blockchain.reactor import BlockchainReactor
from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config import test_config as _test_config
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.events import EventSwitch
from tendermint_tpu.mempool import Mempool
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p import make_connected_switches
from tendermint_tpu.proxy.app_conn import AppConnConsensus, AppConnMempool
from tendermint_tpu.state.state import State
from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS
from tendermint_tpu.types import events as tev

TEST_CHAIN_ID = "reactor_test_chain"


def wait_until(cond, timeout=30.0, tick=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


class Node:
    def __init__(self, cs: ConsensusState, evsw: EventSwitch, mempool: Mempool,
                 store: BlockStore, state: State):
        self.cs = cs
        self.evsw = evsw
        self.mempool = mempool
        self.store = store
        self.state = state
        self.blocks: list = []

    def subscribe_blocks(self) -> None:
        self.evsw.add_listener_for_event(
            "test", tev.EVENT_NEW_BLOCK, lambda d: self.blocks.append(d.block)
        )


def make_genesis(n: int):
    pvs = [PrivValidatorFS(gen_priv_key_ed25519(), None) for _ in range(n)]
    pvs.sort(key=lambda pv: pv.get_address())
    doc = GenesisDoc(
        genesis_time_ns=time.time_ns(),
        chain_id=TEST_CHAIN_ID,
        validators=[GenesisValidator(pv.get_pub_key(), 1, f"v{i}") for i, pv in enumerate(pvs)],
    )
    return doc, pvs


def make_node(doc: GenesisDoc, pv, app=None, consensus_tweak=None) -> Node:
    config = _test_config().consensus
    config.root_dir = tempfile.mkdtemp(prefix="reactor-test-")
    if consensus_tweak is not None:
        consensus_tweak(config)
    app = app if app is not None else CounterApp()
    mtx = threading.RLock()
    mempool = Mempool(_test_config().mempool, AppConnMempool(LocalClient(app, mtx)))
    store = BlockStore(MemDB())
    state = State.get_state(MemDB(), doc)
    evsw = EventSwitch()
    evsw.start()
    cs = ConsensusState(
        config, state, AppConnConsensus(LocalClient(app, mtx)), store, mempool
    )
    cs.set_event_switch(evsw)
    if pv is not None:
        cs.set_priv_validator(pv)
    return Node(cs, evsw, mempool, store, state)


def start_consensus_net(n: int, app_factory=None, switch_factory=None,
                        genesis=None, consensus_tweak=None):
    """genesis=(doc, pvs) overrides make_genesis(n) — e.g. a doc whose
    validator set covers only some of the n nodes (the rest run as full
    nodes until a val-tx adds them). consensus_tweak(config) edits each
    node's consensus config (the preset skips timeout_commit)."""
    doc, pvs = genesis if genesis is not None else make_genesis(n)
    nodes = [make_node(doc, pvs[i], app_factory() if app_factory else None,
                       consensus_tweak)
             for i in range(n)]
    for node in nodes:
        node.subscribe_blocks()

    def init(i, sw):
        node = nodes[i]
        con_r = ConsensusReactor(node.cs, fast_sync=False)
        con_r.set_event_switch(node.evsw)
        sw.add_reactor("CONSENSUS", con_r)
        mem_r = MempoolReactor(_test_config().mempool, node.mempool)
        sw.add_reactor("MEMPOOL", mem_r)
        from tendermint_tpu.p2p.node_info import NodeInfo, default_version

        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"node{i}",
                network=TEST_CHAIN_ID,
                version=default_version("test"),
            )
        )
        return sw

    switches = make_connected_switches(n, init, switch_factory=switch_factory)
    return nodes, switches


def stop_net(nodes, switches):
    for sw in switches:
        sw.stop()
    for node in nodes:
        node.evsw.stop()


# -- consensus reactor --------------------------------------------------------


@pytest.mark.slow
def test_reactor_net_makes_blocks():
    """4 validators over real reactors: every node commits blocks
    (consensus/reactor_test.go:24-79)."""
    nodes, switches = start_consensus_net(4)
    try:
        assert wait_until(
            lambda: all(len(n.blocks) >= 2 for n in nodes), timeout=60
        ), [len(n.blocks) for n in nodes]
        # all nodes agree on block 1's hash
        h1 = [n.store.load_block(1).hash() for n in nodes]
        assert len(set(h1)) == 1
    finally:
        stop_net(nodes, switches)


def test_height_interval_is_commit_timeout_plus_work_not_plus_ticks():
    """A height lasts timeout_commit plus the work of one proposal and
    two rounds of votes. While the gossip routines polled every
    PEER_GOSSIP_SLEEP, that work was three sleeps in series: the median
    interval of this net read timeout_commit + 200-300 ms, in whole
    ticks, and an item of our own waited 30-70 ms for its first send.
    Woken by the event they wait for (round 26) the interval reads
    timeout_commit + 40-50 ms and the items of a height wait 1-3 ms
    together. The 60 ms on the interval are a soft bound, for a box
    busy with other tests (eight busy loops beside this test read 120).
    What fails hard is what the polling routines read: two ticks on the
    interval, or a height's own items waiting half a tick to be sent."""
    import statistics

    from tendermint_tpu.consensus.reactor import PEER_GOSSIP_SLEEP

    timeout_commit = 0.2

    def paced(c):
        c.timeout_commit = timeout_commit
        c.skip_timeout_commit = False
        # a round that times out would be a second cause of long heights
        c.timeout_propose, c.timeout_prevote, c.timeout_precommit = 3.0, 1.0, 1.0

    medians, lags = [], []
    for _attempt in range(3):
        nodes, switches = start_consensus_net(4, consensus_tweak=paced)
        commits: list[float] = []
        nodes[0].evsw.add_listener_for_event(
            "pace", tev.EVENT_NEW_BLOCK, lambda _d: commits.append(time.monotonic())
        )
        try:
            assert wait_until(lambda: len(commits) >= 12, timeout=60), len(commits)
            traces = [t for t in nodes[0].cs.trace.last(12) if 2 <= t.height <= 11]
        finally:
            stop_net(nodes, switches)
        # ten heights, after the one the net connected in
        gaps = [b - a for a, b in zip(commits[1:11], commits[2:12])]
        medians.append(statistics.median(gaps))
        lags.append(statistics.median(
            t.aux.get("gossip_send_lag_s", 0.0) for t in traces
        ))
        assert len(traces) == 10 and any(
            "gossip_send_lag_s" in t.aux for t in traces
        ), "no first send of an own item was noted on ten heights"
        if medians[-1] < timeout_commit + 0.060:
            break
    over = [round((m - timeout_commit) * 1000, 1) for m in medians]
    assert min(lags) < PEER_GOSSIP_SLEEP / 2, (
        f"a height's own items waited {lags} s for their first send"
    )
    assert min(medians) < timeout_commit + 2 * PEER_GOSSIP_SLEEP, (
        f"median height interval {over} ms over timeout_commit in three "
        "nets: the gossip routines are sleeping through what they wait for"
    )
    if min(medians) >= timeout_commit + 0.060:
        pytest.skip(f"slow box: median interval {over} ms over timeout_commit")


@pytest.mark.slow
def test_reactor_net_commits_txs():
    """A tx checked into one node's mempool gossips to the proposer and
    lands in a block everywhere (atomic-broadcast shape)."""
    nodes, switches = start_consensus_net(4, app_factory=KVStoreApp)
    try:
        tx = b"reactor-test-key=reactor-test-value"
        nodes[3].mempool.check_tx(tx)
        assert wait_until(
            lambda: all(
                any(tx in b.data.txs for b in n.blocks) for n in nodes
            ),
            timeout=60,
        ), [sum(len(b.data.txs) for b in n.blocks) for n in nodes]
    finally:
        stop_net(nodes, switches)


# -- fast sync ----------------------------------------------------------------


@pytest.mark.slow
def test_fast_sync_catches_up_and_switches():
    """Node B starts empty with fast_sync=True against node A's chain;
    it downloads+verifies+applies blocks, then switches to consensus
    (blockchain/reactor.go:174-262, 204-217)."""
    doc, pvs = make_genesis(1)
    # -- node A: sole validator, builds a chain by itself
    node_a = make_node(doc, pvs[0])
    # -- node B: non-validator, fast syncs
    node_b = make_node(doc, None)

    def init(i, sw):
        node = (node_a, node_b)[i]
        fast_sync = i == 1
        con_r = ConsensusReactor(node.cs, fast_sync=fast_sync)
        con_r.set_event_switch(node.evsw)
        sw.add_reactor("CONSENSUS", con_r)
        # the reactor owns its own state copy, like the reference's
        # node wiring (node.go:206-227 passes state.Copy() to each)
        bc_r = BlockchainReactor(
            node.state.copy(),
            node.cs.proxy_app_conn,
            node.store,
            fast_sync=fast_sync,
            event_cache=None,
            status_update_interval=0.5,  # test chains move fast
        )
        sw.add_reactor("BLOCKCHAIN", bc_r)
        from tendermint_tpu.p2p.node_info import NodeInfo, default_version

        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"node{i}",
                network=TEST_CHAIN_ID,
                version=default_version("test"),
            )
        )
        return sw

    node_a.subscribe_blocks()
    node_b.subscribe_blocks()
    from tendermint_tpu.p2p import Switch, connect2_switches

    switches = [init(i, Switch()) for i in range(2)]
    for sw in switches:
        sw.start()
    try:
        # A builds its chain alone, then freezes — a fixed catch-up target
        assert wait_until(lambda: node_a.store.height() >= 8, timeout=60)
        node_a.cs.stop()
        target = node_a.store.height()
        connect2_switches(switches, 0, 1)
        assert wait_until(
            lambda: node_b.store.height() >= target, timeout=60
        ), f"B at {node_b.store.height()}, A at {target}"
        got = node_b.store.load_block(2)
        want = node_a.store.load_block(2)
        assert got is not None and got.hash() == want.hash()
        # and B switched over to consensus mode
        con_r_b = switches[1].reactor("CONSENSUS")
        assert wait_until(lambda: not con_r_b.fast_sync, timeout=30)
    finally:
        stop_net([node_a, node_b], switches)


@pytest.mark.slow
def test_reactor_net_commits_under_fuzzed_transport():
    """4 validators whose every p2p stream is wrapped in the chaos fuzz
    layer (random per-op delays, p2p/fuzz.py — the reference's
    FuzzedConnection): consensus must still commit and agree. Guards the
    timeout schedule and gossip against a slow, jittery transport."""
    from tendermint_tpu.p2p import Switch
    from tendermint_tpu.p2p.peer import PeerConfig

    def fuzzy_switch():
        return Switch(peer_config=PeerConfig(
            fuzz=True,
            fuzz_config={"prob_sleep": 0.2, "max_delay": 0.03, "seed": 7},
        ))

    nodes, switches = start_consensus_net(4, switch_factory=fuzzy_switch)
    try:
        assert wait_until(
            lambda: all(len(nd.blocks) >= 3 for nd in nodes), timeout=90
        ), [len(nd.blocks) for nd in nodes]
        h2 = [nd.store.load_block(2).hash() for nd in nodes]
        assert len(set(h2)) == 1
    finally:
        stop_net(nodes, switches)


@pytest.mark.slow
def test_validator_set_change_on_live_net():
    """reference consensus/reactor_test.go:82+ (TestValidatorSetChanges),
    end to end over real reactors: a val-tx through the persistent
    kvstore app adds a live full node to the validator set (EndBlock
    diff -> state.set_block_and_validators, effective next height); the
    new validator starts SIGNING (a later commit carries 3 precommits);
    a power-0 val-tx removes it again and the chain keeps going."""
    from tendermint_tpu.abci.apps.kvstore import PersistentKVStoreApp

    pvs = [PrivValidatorFS(gen_priv_key_ed25519(), None) for _ in range(3)]
    pvs.sort(key=lambda pv: pv.get_address())
    # nodes 0,1 validate (power 10 each); node 2 is a full node whose key
    # joins later with power 4 — quorum (>2/3 of 24 = >16) stays
    # reachable by the two genesis validators, so a lagging newcomer can
    # slow rounds but never halt the chain
    doc = GenesisDoc(
        genesis_time_ns=time.time_ns(),
        chain_id=TEST_CHAIN_ID,
        validators=[
            GenesisValidator(pvs[i].get_pub_key(), 10, f"v{i}") for i in range(2)
        ],
    )
    nodes, switches = start_consensus_net(
        3,
        app_factory=lambda: PersistentKVStoreApp(
            tempfile.mkdtemp(prefix="valchg-")
        ),
        genesis=(doc, pvs),
    )
    try:
        assert wait_until(lambda: nodes[0].store.height() >= 2, timeout=30)
        pub_hex = pvs[2].get_pub_key().raw.hex().upper()
        nodes[0].mempool.check_tx(b"val:" + pub_hex.encode() + b"/4")
        # the set grows to 3 on every node's state
        assert wait_until(
            lambda: all(n.cs.state.validators.size() == 3 for n in nodes),
            timeout=30,
        ), [n.cs.state.validators.size() for n in nodes]
        # ... and the newcomer actually signs: some later commit carries
        # all 3 precommits
        def newcomer_signed():
            h = nodes[0].store.height()
            for height in range(max(2, h - 5), h + 1):
                blk = nodes[0].store.load_block(height)
                if blk is not None and sum(
                    1 for pc in blk.last_commit.precommits if pc is not None
                ) == 3:
                    return True
            return False
        assert wait_until(newcomer_signed, timeout=60)
        # remove it again; the chain keeps committing with the original 2
        nodes[1].mempool.check_tx(b"val:" + pub_hex.encode() + b"/0")
        assert wait_until(
            lambda: all(n.cs.state.validators.size() == 2 for n in nodes),
            timeout=30,
        ), [n.cs.state.validators.size() for n in nodes]
        h_after = nodes[0].store.height()
        assert wait_until(lambda: nodes[0].store.height() >= h_after + 2, timeout=30)
    finally:
        stop_net(nodes, switches)


def test_consensus_catchup_of_behind_peer_on_live_chain():
    """A node far behind that is ALREADY in consensus mode (no fast
    sync) must catch up through the gossip catch-up branches — block
    parts from the peer's store (reactor.go:494-535) and stored-commit
    precommits (reactor.go:637-645) — while the chain KEEPS MOVING.
    This is the safety net under fast-sync's racy IsCaughtUp
    switchover: a restart that flips to consensus mode too early (seen
    in round-4 chaos soaks) must still converge, not stall."""
    doc, pvs = make_genesis(1)
    node_a = make_node(doc, pvs[0])
    node_b = make_node(doc, None)  # non-validator observer

    def init(i, sw):
        node = (node_a, node_b)[i]
        con_r = ConsensusReactor(node.cs, fast_sync=False)
        con_r.set_event_switch(node.evsw)
        sw.add_reactor("CONSENSUS", con_r)
        from tendermint_tpu.p2p.node_info import NodeInfo, default_version

        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"node{i}",
                network=TEST_CHAIN_ID,
                version=default_version("test"),
            )
        )
        return sw

    node_a.subscribe_blocks()
    node_b.subscribe_blocks()
    from tendermint_tpu.p2p import Switch, connect2_switches

    switches = [init(i, Switch()) for i in range(2)]
    for sw in switches:
        sw.start()
    try:
        # A builds a head start alone — and KEEPS COMMITTING throughout
        assert wait_until(lambda: node_a.store.height() >= 6, timeout=60)
        connect2_switches(switches, 0, 1)
        # Phase 1 — live chain: B must make sustained catch-up progress
        # (the round-4 chaos stall was ZERO progress). A at test cadence
        # commits far faster than any real chain, so convergence isn't
        # asserted here — and no absolute height/deadline either (the
        # round-4 advisor flagged `>= 30 within 60s` as flaky on slow
        # machines): require monotonic progress across two samples.
        h0 = node_b.store.height()
        assert wait_until(
            lambda: node_b.store.height() > h0, timeout=90
        ), f"B made no progress from {h0}, A at {node_a.store.height()}"
        h1 = node_b.store.height()
        assert wait_until(
            lambda: node_b.store.height() > h1, timeout=90
        ), f"B stalled at {h1} after initial progress, A at {node_a.store.height()}"
        # Phase 2 — production pauses (real chains commit ~1/s; catch-up
        # is ~10x that): B must fully converge to A's tip.
        node_a.cs.stop()
        target = node_a.store.height()
        assert wait_until(
            lambda: node_b.store.height() >= target, timeout=120
        ), f"B stalled at {node_b.store.height()}, target {target}"
        got = node_b.store.load_block(3)
        want = node_a.store.load_block(3)
        assert got is not None and got.hash() == want.hash()
    finally:
        stop_net([node_a, node_b], switches)


def test_fast_sync_rides_the_tpu_gateway(monkeypatch):
    """Regression: fast sync with the gateway wired (as node/node.py wires
    it) must actually route commit signatures AND part hashing through the
    batched kernels — the stats counters move, and the synced chain is
    byte-identical to the builder's (blockchain/reactor.go:229-236)."""
    from tendermint_tpu.ops import gateway

    # close/fatal tracer for the intermittent both-peers-drop flake
    # ("stream closed" on both sides, full-suite-only): record who closes
    # streams and why connections die, dump on failure
    import traceback as _tb

    from tendermint_tpu.p2p import conn as _conn
    from tendermint_tpu.p2p import stream as _stream

    trace: list = []
    orig_close = _stream.SocketStream.close
    orig_fatal = _conn.MConnection._fatal

    def traced_close(self):
        trace.append(
            (time.monotonic(), "close", repr(self.sock),
             "".join(_tb.format_stack(limit=8)[:-1])[-600:])
        )
        return orig_close(self)

    def traced_fatal(self, exc):
        trace.append(
            (time.monotonic(), "fatal", f"{type(exc).__name__}: {exc}",
             "".join(_tb.format_stack(limit=8)[:-1])[-600:])
        )
        return orig_fatal(self, exc)

    monkeypatch.setattr(_stream.SocketStream, "close", traced_close)
    monkeypatch.setattr(_conn.MConnection, "_fatal", traced_fatal)

    verifier = gateway.Verifier(min_tpu_batch=1, use_tpu=True)
    hasher = gateway.Hasher(min_tpu_batch=1, use_tpu=True)

    doc, pvs = make_genesis(1)
    node_a = make_node(doc, pvs[0])
    node_b = make_node(doc, None)

    def init(i, sw):
        node = (node_a, node_b)[i]
        fast_sync = i == 1
        con_r = ConsensusReactor(node.cs, fast_sync=fast_sync)
        con_r.set_event_switch(node.evsw)
        sw.add_reactor("CONSENSUS", con_r)
        bc_r = BlockchainReactor(
            node.state.copy(),
            node.cs.proxy_app_conn,
            node.store,
            fast_sync=fast_sync,
            event_cache=None,
            batch_verifier=verifier.commit_batch_verifier() if fast_sync else None,
            async_batch_verifier=verifier.verify_batch_async if fast_sync else None,
            part_hasher=hasher.part_leaf_hashes if fast_sync else None,
            status_update_interval=0.5,
        )
        sw.add_reactor("BLOCKCHAIN", bc_r)
        from tendermint_tpu.p2p.node_info import NodeInfo, default_version

        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"node{i}",
                network=TEST_CHAIN_ID,
                version=default_version("test"),
            )
        )
        return sw

    from tendermint_tpu.p2p import Switch, connect2_switches

    switches = [init(i, Switch()) for i in range(2)]
    for sw in switches:
        sw.start()
    try:
        assert wait_until(lambda: node_a.store.height() >= 4, timeout=120)
        node_a.cs.stop()
        target = node_a.store.height()
        connect2_switches(switches, 0, 1)
        if not wait_until(lambda: node_b.store.height() >= target, timeout=120):
            # stall diagnostics: the flake signature is B stuck at 0 under
            # heavy parallel load — record enough to tell "never connected"
            # from "connected but no requests" from "requests but no blocks"
            bc_b = switches[1].reactors.get("BLOCKCHAIN")
            from collections import Counter

            names = Counter(
                t.name.split("-")[0].split(".")[0] for t in threading.enumerate()
            )
            tr = "\n".join(
                f"  t={t:.3f} {kind} {what}\n{stack}" for t, kind, what, stack in trace
            )
            raise AssertionError(
                f"B at {node_b.store.height()}, A at {target}; "
                f"peers A={switches[0].peers.size()} B={switches[1].peers.size()}; "
                f"B pool height={bc_b.pool.height} "
                f"requesters={len(bc_b.pool.requesters)} "
                f"max_peer_height={bc_b.pool.max_peer_height}; "
                f"B synced={bc_b.blocks_synced}; "
                f"threads={threading.active_count()} {dict(names.most_common(8))}\n"
                f"close/fatal trace ({len(trace)} events):\n{tr}"
            )
        for h in range(1, target + 1):
            assert node_b.store.load_block(h).hash() == node_a.store.load_block(h).hash()
        vstats, hstats = verifier.stats(), hasher.stats()
        assert vstats["tpu_sigs"] > 0, vstats  # commit sigs rode the kernel
        assert vstats["tpu_batches"] > 0, vstats
        assert hstats["tpu_part_batches"] > 0, hstats  # part hashing did too
        assert hstats["tpu_leaves"] > 0, hstats
    finally:
        stop_net([node_a, node_b], switches)


# -- mempool reactor ----------------------------------------------------------


def test_mempool_reactor_gossips_txs():
    """Tx checked on one node appears in the other's mempool."""
    doc, _pvs = make_genesis(1)
    n1, n2 = make_node(doc, None, CounterApp()), make_node(doc, None, CounterApp())

    def init(i, sw):
        node = (n1, n2)[i]
        sw.add_reactor("MEMPOOL", MempoolReactor(_test_config().mempool, node.mempool))
        from tendermint_tpu.p2p.node_info import NodeInfo, default_version

        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"m{i}",
                network=TEST_CHAIN_ID,
                version=default_version("test"),
            )
        )
        return sw

    switches = make_connected_switches(2, init)
    try:
        tx = (0).to_bytes(8, "big")  # counter app wants ordered u64 txs
        n1.mempool.check_tx(tx)
        assert wait_until(lambda: n2.mempool.size() == 1, timeout=10)
        assert n2.mempool.reap(10) == [tx]
    finally:
        stop_net([n1, n2], switches)


def test_speculative_group_spans_never_overshoot():
    """Grouping must stop BEFORE exceeding group_sig_target so dispatches
    stay in the intended power-of-two kernel bucket (code-review r3)."""
    from tendermint_tpu.blockchain.reactor import group_spans

    # 1000-validator commits, target 4096: groups of 4, never 5
    assert group_spans([1000] * 9, 4096) == [(0, 4), (4, 8), (8, 9)]
    # one commit larger than the target still goes alone
    assert group_spans([5000, 100, 100], 4096) == [(0, 1), (1, 3)]
    # small commits pack tightly up to the boundary
    assert group_spans([1024] * 4, 4096) == [(0, 4)]
    assert group_spans([1025] * 4, 4096) == [(0, 3), (3, 4)]
    assert group_spans([], 4096) == []


def test_pool_counts_blocks_dropped_as_unsolicited():
    """A block from a peer its request no longer names, a block nobody
    asked for and a block that comes twice are downloaded, decoded and
    thrown away: fastsync_blocks_dropped_unsolicited counts them (fault 1
    of PERF.md section 7 was computed from bytes until now)."""
    from types import SimpleNamespace

    from tendermint_tpu.blockchain.pool import BlockPool, BpRequester

    pool = BlockPool(5, lambda h, p: None, lambda p, r: None)
    pool.set_peer_height("a", 10)
    pool.set_peer_height("b", 10)
    req = pool.requesters[5] = BpRequester(5)
    req.peer_id = "a"
    block = SimpleNamespace(header=SimpleNamespace(height=5))
    assert pool.dropped_unsolicited == 0
    pool.add_block("b", block, 100)          # the request names peer a
    assert pool.dropped_unsolicited == 1 and req.block is None
    pool.add_block("a", SimpleNamespace(header=SimpleNamespace(height=9)), 100)
    assert pool.dropped_unsolicited == 2     # no request at that height
    pool.add_block("a", block, 100)          # the one that was asked for
    assert pool.dropped_unsolicited == 2 and req.block is block
    pool.add_block("a", block, 100)          # and again
    assert pool.dropped_unsolicited == 3


def test_reactor_times_block_decode_as_a_stage():
    """json.loads + Block.from_json of a block_response lay outside the
    five fastsync_*_s stages; `decode` is the sixth, and only a
    block_response adds to it."""
    import json as _json

    from tendermint_tpu.blockchain.reactor import BlockchainReactor

    doc, pvs = make_genesis(1)
    node = make_node(doc, pvs[0])
    bc = BlockchainReactor(
        node.state.copy(), node.cs.proxy_app_conn, node.store, fast_sync=True,
    )
    assert bc.stage_s["decode"] == 0.0 and len(bc.stage_s) == 6
    got = []

    class _Pool:
        dropped_unsolicited = 0

        def add_block(self, peer_id, block, size):
            got.append((peer_id, block.header.height, size))

        def peer_has_no_block(self, peer_id, height):
            pass

    class _Peer:
        def id(self):
            return "peer-1"

    errors = []
    bc.pool = _Pool()
    bc.switch = type("S", (), {"stop_peer_for_error":
                               lambda self, peer, exc: errors.append(exc)})()
    from tendermint_tpu.types.block import Block, empty_commit
    from tendermint_tpu.types.block_id import BlockID

    block, _parts = Block.make_block(
        1, doc.chain_id, [b"k=v"], empty_commit(), BlockID(),
        node.state.validators.hash(), b"", 65536)
    raw = _json.dumps({"type": "block_response",
                       "block": block.to_json()}).encode()
    bc.receive(0x40, _Peer(), raw)
    assert not errors, errors
    assert got == [("peer-1", 1, len(raw))]
    first = bc.stage_s["decode"]
    assert first > 0.0
    bc.receive(0x40, _Peer(), _json.dumps(
        {"type": "no_block_response", "height": 3}).encode())
    assert bc.stage_s["decode"] == first
    bc.receive(0x40, _Peer(), raw)
    assert bc.stage_s["decode"] > first


def test_fastsync_flag_clears_on_switchover():
    """/metrics fastsync_active must go 0 once the node switches to
    consensus (code-review r3: the constructor flag was never cleared)."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor

    doc, pvs = make_genesis(1)
    node = make_node(doc, pvs[0])
    bc = BlockchainReactor(
        node.state.copy(), node.cs.proxy_app_conn, node.store, fast_sync=True,
        status_update_interval=0.05,
    )

    class _FakePool:
        def is_running(self):
            return True

        def is_caught_up(self):
            return True

        def stop(self):
            pass

        def peek_blocks(self, n):
            return []

        def peek_two_blocks(self):
            return (None, None)

    class _FakeSwitch:
        def reactor(self, name):
            return None

        def broadcast(self, *a, **k):
            return []

    bc.pool = _FakePool()
    bc.switch = _FakeSwitch()
    bc._started = True  # the routine guards on is_running()
    assert bc.fast_sync is True
    bc._pool_routine()  # caught up immediately -> switchover path
    assert bc.fast_sync is False


def test_vote_gossip_marks_peer_only_on_successful_send():
    """pick_vote_to_send must NOT mark the peer as having the vote —
    the mark lands in _send_vote only AFTER peer.send succeeds
    (reactor.go PickSendVote's order). Marking at pick time meant a
    vote whose send failed on a full channel queue (exactly the
    burst-load moment) was skipped for that peer forever; with no other
    resend mechanism a 2-2 height split could wedge the whole net — the
    netchaos smoke's stall signature."""
    from tendermint_tpu.consensus.reactor import ConsensusReactor, PeerState
    from tendermint_tpu.libs.bitarray import BitArray
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT

    class _Vote:
        height, round_, type_, validator_index = 5, 0, VOTE_TYPE_PRECOMMIT, 1

        def to_json(self):
            return {"height": self.height}

    class _VoteSet:
        height, round_, type_ = 5, 0, VOTE_TYPE_PRECOMMIT

        def size(self):
            return 4

        def bit_array(self):
            ba = BitArray(4)
            ba.set_index(1, True)
            return ba

        def get_by_index(self, index):
            assert index == 1
            return _Vote()

    class _Peer:
        def __init__(self, ok):
            self.ok = ok
            self.sent = 0

        def try_send(self, ch, raw):
            self.sent += 1
            return self.ok

    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 5, 0
    ps.ensure_vote_bit_arrays(5, 4)
    vs = _VoteSet()
    picks0 = ps.m_vote_picks.value
    sends0 = ps.m_vote_sends.value
    fails0 = ps.m_vote_send_failures.value

    # pick alone must not mark: the same vote stays pickable
    assert ps.pick_vote_to_send(vs) is not None
    assert ps.pick_vote_to_send(vs) is not None
    assert ps.m_vote_picks.value == picks0  # picking alone never counts

    # failed send: bit stays clear, the vote is retried later — AND the
    # per-peer failure counter moves (round 15: the scrape-visible form
    # of the PR-13 wedge — picks outrunning sends)
    failing = _Peer(ok=False)
    assert not ConsensusReactor._send_vote(None, failing, ps, _Vote())
    assert failing.sent == 1
    assert ps.pick_vote_to_send(vs) is not None, (
        "a failed send must leave the vote pickable"
    )
    assert ps.m_vote_picks.value == picks0 + 1
    assert ps.m_vote_sends.value == sends0
    assert ps.m_vote_send_failures.value == fails0 + 1, (
        "a failed vote send must increment the per-peer failure counter"
    )

    # successful send: marked, never picked again
    assert ConsensusReactor._send_vote(None, _Peer(ok=True), ps, _Vote())
    assert ps.pick_vote_to_send(vs) is None
    assert ps.m_vote_picks.value == picks0 + 2
    assert ps.m_vote_sends.value == sends0 + 1
    assert ps.m_vote_send_failures.value == fails0 + 1


def test_last_commit_gossip_reaches_peer_in_a_later_round():
    """The 2-2 wedge mechanism (netchaos stall): a laggard one height
    behind whose ROUND raced past the commit round (it timed out
    waiting for exactly these votes) had no tracking bit array — the
    last-commit gossip branch silently sent nothing, and with the ahead
    nodes unable to advance (no quorum), the >= +2 stored-commit
    catchup never engaged. The gossip branch now ensures the
    catchup-commit array at the commit's round first."""
    from tendermint_tpu.consensus.reactor import PeerState
    from tendermint_tpu.libs.bitarray import BitArray
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT

    class _LastCommit:
        height, round_, type_ = 5, 0, VOTE_TYPE_PRECOMMIT

        def size(self):
            return 4

        def bit_array(self):
            ba = BitArray(4)
            for i in range(3):
                ba.set_index(i, True)
            return ba

        def get_by_index(self, index):
            return ("vote", index)

    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 5, 2  # raced past commit round 0
    ps.ensure_vote_bit_arrays(5, 4)     # tracks round 2, not round 0
    catchups0 = ps.m_catchup_commits.value
    # the hole: without a catchup array at round 0, nothing is pickable
    assert ps.pick_vote_to_send(_LastCommit()) is None
    # the fix: the height+1 gossip branch ensures the catchup round —
    # and the engagement is COUNTED per peer (round 15: the catchup
    # signal a fleet scrape alarms on instead of a frozen height vector)
    ps.ensure_catchup_commit_round(5, 0, 4)
    assert ps.m_catchup_commits.value == catchups0 + 1
    # re-ensuring the SAME round is a no-op, not a recount
    ps.ensure_catchup_commit_round(5, 0, 4)
    assert ps.m_catchup_commits.value == catchups0 + 1
    assert ps.pick_vote_to_send(_LastCommit()) is not None
    # and marking via set_has_vote lands in the SAME tracking array
    ps.set_has_vote(5, 0, VOTE_TYPE_PRECOMMIT, 0)
    ps.set_has_vote(5, 0, VOTE_TYPE_PRECOMMIT, 1)
    ps.set_has_vote(5, 0, VOTE_TYPE_PRECOMMIT, 2)
    assert ps.pick_vote_to_send(_LastCommit()) is None
