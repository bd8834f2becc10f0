"""A committee on the normal path (PR 27): the plain quorum reference of
the benchmark against hand-built commits, ten real reactors over loopback
TCP with the batched vote plane taken, a forged precommit inside a burst,
and the daemon's merge of waiting verify requests (each caller gets the
verdicts of its own lanes)."""

from __future__ import annotations

import copy
import os
import socket
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from reference import commit_ref, ed25519_ref  # noqa: E402

from consensus_common import TEST_CHAIN_ID, make_cs_and_stubs  # noqa: E402
from tendermint_tpu import devd, devd_spans  # noqa: E402
from tendermint_tpu.consensus import messages as msgs  # noqa: E402
from tendermint_tpu.consensus.state import MsgInfo  # noqa: E402
from tendermint_tpu.crypto import ed25519 as ed  # noqa: E402
from tendermint_tpu.ops import gateway  # noqa: E402
from tendermint_tpu.types import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE  # noqa: E402

CHAIN = "committee-test-chain"


def wait_until(cond, timeout=60.0, tick=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


# -- (a) the plain reference against hand-built commits ------------------------

N_REF = 6   # equal power: 4 of 6 is exactly two thirds
BLOCK = {"hash": "AB" * 20, "parts": {"total": 1, "hash": "CD" * 20}}
OTHER = {"hash": "EF" * 20, "parts": {"total": 1, "hash": "CD" * 20}}


def ref_genesis(n=N_REF, power=10):
    secrets = [bytes([i + 1]) * 32 for i in range(n)]
    gen = {"validators": [
        {"pub_key": [1, ed25519_ref.public_key(s).hex().upper()],
         "power": power, "name": f"v{i}"} for i, s in enumerate(secrets)]}
    vals = commit_ref.validator_set(gen)
    by_key = {ed25519_ref.public_key(s): s for s in secrets}
    return vals, [by_key[v["pub_key"]] for v in vals]


def precommit(vals, secrets, idx, block_id=BLOCK, height=7, round_=0, type_=2):
    vote = {"validator_address": vals[idx]["address"].hex().upper(),
            "validator_index": idx, "height": height, "round": round_,
            "type": type_, "block_id": copy.deepcopy(block_id)}
    sig = ed25519_ref.sign(secrets[idx], commit_ref.sign_bytes(CHAIN, vote))
    vote["signature"] = [1, sig.hex().upper()]
    return vote


def commit_of(vals, secrets, signers, **kw):
    return {"block_id": copy.deepcopy(BLOCK),
            "precommits": [precommit(vals, secrets, i, **kw) if i in signers
                           else None for i in range(len(vals))]}


def _forged(c, vals, secrets):
    sig = bytearray(bytes.fromhex(c["precommits"][4][
        "signature"][1]))
    sig[3] ^= 0x10
    c["precommits"][4]["signature"][1] = bytes(sig).hex().upper()


def _other_block(c, vals, secrets):
    c["precommits"][4] = precommit(vals, secrets, 4, block_id=OTHER)


def _counted_twice(c, vals, secrets):
    c["precommits"][4] = precommit(vals, secrets, 0)   # validator 0 again


def _prevote(c, vals, secrets):
    c["precommits"][4] = precommit(vals, secrets, 4, type_=1)


def _other_height(c, vals, secrets):
    c["precommits"][4] = precommit(vals, secrets, 4, height=8)


def _wrong_address(c, vals, secrets):
    c["precommits"][4]["validator_address"] = vals[3]["address"].hex().upper()


def test_reference_exactly_two_thirds_fails_and_one_more_passes():
    vals, secrets = ref_genesis()
    four = commit_ref.check_commit(CHAIN, vals, 7, BLOCK,
                                   commit_of(vals, secrets, {0, 1, 2, 3}))
    assert four["power_valid"] == 40 and four["power_total"] == 60
    assert four["quorum"] is False and four["refused"] == []
    five = commit_ref.check_commit(CHAIN, vals, 7, BLOCK,
                                   commit_of(vals, secrets, {0, 1, 2, 3, 4}))
    assert five["quorum"] is True and five["counted"] == 5


@pytest.mark.parametrize("spoil,why", [
    (_forged, "signature does not verify"),
    (_other_block, "another block id"),
    (_counted_twice, "lane and index differ"),
    (_prevote, "not a precommit"),
    (_other_height, "another height"),
    (_wrong_address, "address does not stand at its index"),
])
def test_reference_does_not_count_a_spoilt_lane(spoil, why):
    """Five of six would pass; with lane 4 spoilt four are left, which is
    exactly two thirds and fails."""
    vals, secrets = ref_genesis()
    c = commit_of(vals, secrets, {0, 1, 2, 3, 4})
    spoil(c, vals, secrets)
    out = commit_ref.check_commit(CHAIN, vals, 7, BLOCK, c)
    assert out["refused"] == [[4, why]]
    assert out["counted"] == 4 and out["quorum"] is False


def test_reference_sign_bytes_are_the_programs():
    """Independent code, same bytes: else every live commit would fail."""
    from tendermint_tpu.types import BlockID, PartSetHeader, Vote

    bid = BlockID(bytes.fromhex(BLOCK["hash"]),
                  PartSetHeader(1, bytes.fromhex(BLOCK["parts"]["hash"])))
    vote = Vote(b"\x01" * 20, 3, 7, 2, VOTE_TYPE_PRECOMMIT, bid)
    assert commit_ref.sign_bytes(CHAIN, vote.to_json()) == vote.sign_bytes(CHAIN)
    nil = Vote(b"\x01" * 20, 3, 7, 2, VOTE_TYPE_PREVOTE, BlockID())
    assert commit_ref.sign_bytes(CHAIN, nil.to_json()) == nil.sign_bytes(CHAIN)


# -- (b) ten real reactors over loopback TCP -----------------------------------

N_NET = 10
HEIGHTS = 5
GENESIS_NS = 1_700_000_000 * 10**9


def _tcp_connect(switches, i, j):
    """Full peering of two switches over a loopback TCP connection."""
    from tendermint_tpu.p2p.stream import SocketStream

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    out = socket.create_connection(srv.getsockname())
    inc, _ = srv.accept()
    srv.close()
    errs = []

    def add(sw, sock, outbound):
        try:
            sw.add_peer_from_stream(SocketStream(sock), outbound=outbound)
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    ts = [threading.Thread(target=add, args=(switches[i], out, True), daemon=True),
          threading.Thread(target=add, args=(switches[j], inc, False), daemon=True)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    if errs:
        raise errs[0]


def _commit_only_with_every_precommit(cs) -> None:
    """Which precommits a LastCommit holds is decided by which had arrived
    when their node committed (a straggler is not sent after a node that
    has moved on), so two runs differ there by chance. Here a node commits
    when ALL ten are in: the same ten lanes in every commit of both runs,
    and then the same bytes, if batching changes nothing."""
    real = cs.enter_commit

    def enter_commit(height, round_):
        precommits = cs.rs.votes.precommits(round_)
        if precommits is None or precommits.has_all():
            real(height, round_)

    cs.enter_commit = enter_commit


def _committee_chain(batching: bool):
    """Ten validators from fixed seeds, fixed block times, no writes: five
    heights; returns (blocks as bytes, commits as JSON, genesis JSON, the
    nodes' batcher counters)."""
    import test_reactors as tr
    from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
    from tendermint_tpu.p2p.switch import make_connected_switches
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS

    pvs = sorted((PrivValidatorFS(gen_priv_key_ed25519(b"committee-%d" % i), None)
                  for i in range(N_NET)), key=lambda pv: pv.get_address())
    doc = GenesisDoc(
        genesis_time_ns=GENESIS_NS, chain_id=tr.TEST_CHAIN_ID,
        validators=[GenesisValidator(pv.get_pub_key(), 10, f"v{i}")
                    for i, pv in enumerate(pvs)])

    def paced(c):
        c.timeout_commit, c.skip_timeout_commit = 0.3, False
        c.timeout_propose, c.timeout_prevote, c.timeout_precommit = 6.0, 3.0, 3.0

    real = make_connected_switches
    tr.make_connected_switches = \
        lambda n, init, switch_factory=None: real(n, init, connect=_tcp_connect,
                                                  switch_factory=switch_factory)
    try:
        nodes = [tr.make_node(doc, pvs[i], None, paced) for i in range(N_NET)]
        for nd in nodes:
            nd.cs.vote_batching = batching
            nd.cs.propose_time_source = lambda h: GENESIS_NS + h * 10**9
            _commit_only_with_every_precommit(nd.cs)
        # start_consensus_net builds its own nodes: give it ours
        made = iter(nodes)
        real_make = tr.make_node
        tr.make_node = lambda *a, **k: next(made)
        try:
            nodes, switches = tr.start_consensus_net(N_NET, genesis=(doc, pvs))
        finally:
            tr.make_node = real_make
    finally:
        tr.make_connected_switches = real
    try:
        assert wait_until(
            lambda: all(n.store.height() >= HEIGHTS + 1 for n in nodes), 150), \
            [n.store.height() for n in nodes]
        blocks = [[n.store.load_block(h).to_bytes() for h in range(1, HEIGHTS + 1)]
                  for n in nodes]
        commits = [nodes[0].store.load_block_commit(h).to_json()
                   for h in range(1, HEIGHTS + 1)]
        metas = [nodes[0].store.load_block_meta(h).block_id.to_json()
                 for h in range(1, HEIGHTS + 1)]
        counters = [(n.cs.vote_batcher.batches, n.cs.vote_batcher.batched_sigs,
                     n.cs.vote_batcher.singletons) for n in nodes]
        traces = [t.to_json() for t in nodes[0].cs.trace.last(HEIGHTS + 2)]
    finally:
        tr.stop_net(nodes, switches)
    return blocks, commits, metas, doc.to_json(), counters, traces


def _full_chain(batching: bool):
    """A run in which every commit holds all ten precommits FOR THE BLOCK
    (a validator that a busy box kept waiting past a time-out precommits
    nil, and its lane then differs by chance, not by batching)."""
    for _attempt in range(3):
        run = _committee_chain(batching)
        block_ids = run[2]
        if all(len([p for p in c["precommits"]
                    if p and p["block_id"]["hash"] == bid["hash"]]) == N_NET
               for c, bid in zip(run[1], block_ids)):
            return run
    pytest.fail("three runs in a row held a nil precommit")


@pytest.fixture(scope="module")
def committee_runs():
    return _full_chain(True), _full_chain(False)


def test_committee_of_ten_takes_the_batched_path_on_every_node(committee_runs):
    (blocks, _c, _m, _g, counters, traces), _ = committee_runs
    assert all(b == blocks[0] for b in blocks)          # one chain on ten nodes
    assert all(batches > 0 for batches, _s, _one in counters), counters
    # the height's trace carries the counters the benchmark reads
    aux = [t["aux"] for t in traces]
    assert any(a.get("vote_batches", 0) >= 1 and a.get("votes_batched", 0) >= 2
               for a in aux)
    assert all(a.get("votes_received", 0) >= 2 * (N_NET - 1) - 2 for a in aux[1:-1])
    assert all(a["cpu_s"] > 0 for a in aux)
    assert any(a.get("commit_verify_lanes", 0) >= N_NET * 2 // 3 for a in aux)


def test_every_commit_of_the_committee_passes_the_plain_reference(committee_runs):
    (_b, commits, metas, genesis, _c, _t), _ = committee_runs
    vals = commit_ref.validator_set(genesis)
    for h, (c, block_id) in enumerate(zip(commits, metas), start=1):
        out = commit_ref.check_commit(genesis["chain_id"], vals, h, block_id, c)
        assert out["quorum"] and not out["refused"], (h, out)
        assert out["counted"] == N_NET


def test_chain_is_byte_identical_with_vote_batching_off(committee_runs):
    (on, *_rest_on), (off, _c, _m, _g, counters_off, _t) = committee_runs
    assert all(batches == 0 for batches, _s, _one in counters_off)
    assert on[0] == off[0]


# -- (c) a forged precommit inside a burst -------------------------------------


def test_forged_precommit_in_a_burst_loses_its_lane_and_the_height_commits(caplog):
    import logging

    from test_vote_batch import _forge

    caplog.set_level(logging.WARNING)

    n = 10
    cs, stubs, prop_idx = make_cs_and_stubs(n)
    cs.start()
    try:
        assert wait_until(lambda: cs.rs.proposal_block is not None
                          and cs.rs.proposal_block_parts is not None, 30)
        from tendermint_tpu.types import BlockID

        bid = BlockID(cs.rs.proposal_block.hash(),
                      cs.rs.proposal_block_parts.header())
        others = [s for s in stubs if s.index != prop_idx]
        for s in others:
            cs._inputs.put(("msg", MsgInfo(msgs.VoteMessage(
                s.sign_vote(VOTE_TYPE_PREVOTE, TEST_CHAIN_ID, bid)), "peer-test")))
        assert wait_until(lambda: cs.rs.votes.precommits(0) is not None
                          and cs.rs.votes.precommits(0).get_by_index(prop_idx)
                          is not None, 30)
        burst = [s.sign_vote(VOTE_TYPE_PRECOMMIT, TEST_CHAIN_ID, bid) for s in others]
        liar = burst[4].validator_index
        sent = list(burst)
        sent[4] = _forge(burst[4])
        b0 = cs.vote_batcher.batches
        for v in sent:
            cs._inputs.put(("msg", MsgInfo(msgs.VoteMessage(v), "peer-test")))
        assert wait_until(lambda: cs.block_store.height() >= 1, 60)
        assert cs.vote_batcher.batches > b0           # the burst rode a batch
        # exactly that lane was refused: one bad vote, and it is the liar's
        bad_votes = [r for r in caplog.records if "bad vote" in r.getMessage()]
        assert len(bad_votes) == 1, [r.getMessage() for r in bad_votes]
        seen = cs.block_store.load_seen_commit(1)
        held = {pc.validator_index for pc in seen.precommits if pc is not None}
        # (a node commits at more than two thirds; who came later is not in)
        assert liar not in held and len(held) >= 7 and held <= set(range(n))
        # the reference agrees: every lane held counts, and the forged vote
        # put back into its lane is the one lane it refuses
        vals = commit_ref.validator_set(cs.state.genesis_doc.to_json())
        block_id = cs.block_store.load_block_meta(1).block_id.to_json()
        good = commit_ref.check_commit(TEST_CHAIN_ID, vals, 1, block_id,
                                       seen.to_json())
        assert good["quorum"] and good["counted"] == len(held)
        assert not good["refused"]
        with_liar = seen.to_json()
        with_liar["precommits"][liar] = sent[4].to_json()
        bad = commit_ref.check_commit(TEST_CHAIN_ID, vals, 1, block_id, with_liar)
        assert bad["refused"] == [[liar, "signature does not verify"]]
        assert bad["counted"] == len(held) and bad["quorum"]
    finally:
        cs.stop()


# -- (d) the daemon's merge of waiting verify requests --------------------------


def _items(n: int, tag: bytes, forge: int | None = None):
    seeds = [bytes([9, k]) + b"\x09" * 30 for k in range(4)]
    out = []
    for i in range(n):
        msg = tag + b"-%d" % i
        sig = ed.sign(seeds[i % 4], msg)
        if i == forge:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        out.append((ed.public_key(seeds[i % 4]), msg, sig))
    return out


class _Gate:
    """The daemon's verifier with its first call held until told: what
    arrives meanwhile has to wait, and so rides one program."""

    def __init__(self):
        self.inner = gateway.Verifier(use_tpu=False)
        self.calls: list[int] = []
        self.open = threading.Event()
        self.entered = threading.Event()

    def verify_batch(self, items):
        items = list(items)
        self.calls.append(len(items))
        if len(self.calls) <= devd.MERGE_TURNS:
            self.entered.set()
            assert self.open.wait(30)
        if any(it[1] == b"poison" for it in items):
            raise ValueError("a lane the kernel cannot take")
        return self.inner.verify_batch(items)


def _merge_under_gate(requests):
    """Send `requests` (lists of items) through one daemon state's merger
    so that all but the first MERGE_TURNS wait together. Returns (results
    or exceptions in order, the gate, the records, the state)."""
    st = devd._DaemonState()
    gate = _Gate()
    st.verifier = gate
    st.merger._widths_run = {8, 16, 32, 64, 128, 256}   # a warmed daemon
    out: list = [None] * len(requests)
    recs = []

    def call(k):
        rec = st.spans.begin(k + 1)
        st.spans.decoded(rec, "verify", len(requests[k]), f"t-{k}")
        recs.append(rec)
        try:
            out[k] = st.merger.verify(requests[k], rec, k + 1)
        except Exception as exc:  # noqa: BLE001
            out[k] = exc
        st.spans.finish(rec)

    threads = [threading.Thread(target=call, args=(k,), daemon=True)
               for k in range(len(requests))]
    for t in threads[:devd.MERGE_TURNS]:
        t.start()
        time.sleep(0.05)
    assert gate.entered.wait(10)
    assert wait_until(lambda: len(gate.calls) == devd.MERGE_TURNS, 10)
    for t in threads[devd.MERGE_TURNS:]:
        t.start()
    assert wait_until(
        lambda: len(st.merger._queue) == len(requests) - devd.MERGE_TURNS, 10)
    gate.open.set()
    for t in threads:
        t.join(30)
    rows = [dict(zip(devd_spans.FIELDS, r)) for r in st.spans.rows()]
    return out, gate, rows, st


def test_merged_program_gives_each_caller_its_own_lanes_verdicts():
    """Six callers wait together; one of them holds a forged lane. It is
    False in that caller's answer, at its place, and nowhere else."""
    reqs = [_items(1, b"lead-a"), _items(1, b"lead-b")] + \
           [_items(3, b"r%d" % k, forge=1 if k == 2 else None) for k in range(6)]
    out, gate, rows, st = _merge_under_gate(reqs)
    assert out[0] == [True] and out[1] == [True]
    for k in range(6):
        want = [True, k != 2, True]
        assert out[2 + k] == want, (k, out[2 + k])
    # the six that waited rode one program of 18 lanes over six connections
    assert gate.calls == [1, 1, 18]       # 18 lanes: 32 wide, a width run
    assert st.merger.stats == {"programs": 3, "requests": 8, "lanes": 20,
                               "merged_programs": 1, "requests_max": 6}
    merged = [r for r in rows if r["merged"] == 6]
    assert len(merged) == 6 and {r["merged_conns"] for r in merged} == {6}
    assert {r["program_lanes"] for r in merged} == {18}
    assert len({r["program"] for r in merged}) == 1
    lead = [r for r in merged if r["program"] == r["seq"]]
    assert len(lead) == 1
    for r in rows:      # the phases still partition every record's call
        ts = [r[k] for k in ("t_recv0", "t_decoded", "t_marshalled",
                             "t_dispatched", "t_verdicts", "t_replied")]
        assert ts == sorted(ts) and ts[0] > 0
    alone = [r for r in rows if r["merged"] == 1]
    assert len(alone) == 2 and all(r["program"] == r["seq"] for r in alone)


def test_a_request_the_kernel_refuses_fails_alone():
    poison = [(b"\x00" * 32, b"poison", b"\x00" * 64)]
    reqs = [_items(1, b"la"), _items(1, b"lb"), _items(2, b"ok1"), poison,
            _items(2, b"ok2", forge=0)]
    out, gate, _rows, _st = _merge_under_gate(reqs)
    assert out[2] == [True, True] and out[4] == [False, True]
    assert isinstance(out[3], ValueError)
    # the merged program of 5 lanes raised; each request then ran alone
    assert gate.calls == [1, 1, 5, 2, 1, 2]


def test_a_merged_program_never_passes_the_cap_nor_makes_a_new_width():
    def queue(m, sizes):
        m._queue = [devd._Waiting([None] * n, None, k) for k, n in enumerate(sizes)]

    def take(m):
        return [len(w.items) for w in m._take()]

    assert [devd._width(n) for n in (1, 8, 9, 16, 17, 200, 256, 257)] == \
        [8, 8, 16, 16, 32, 256, 256, 512]
    m = devd._DaemonState().merger
    m._widths_run = {8, 16, 32, 64, 128, 256}      # a daemon warmed that far
    queue(m, [100, 100, 100, 40, 300, 16])
    first = take(m)
    assert first == [100, 100, 40, 16] and sum(first) <= devd.MERGE_MAX_LANES
    assert take(m) == [100]
    assert take(m) == [300]                        # wider than the cap: alone
    # a daemon that has run 8- and 16-wide programs only: merging stops
    # where the next width would be a program to compile
    m._widths_run = {8, 16}
    queue(m, [1, 3, 4, 5, 3, 9, 2])
    assert take(m) == [1, 3, 4, 5, 3]              # 16 lanes: a width it has
    assert take(m) == [9, 2]                       # 11 lanes, still 16 wide
    # nothing run yet: requests join only inside the first one's own width
    m._widths_run = set()
    queue(m, [2, 5, 1, 9])
    assert take(m) == [2, 5, 1] and take(m) == [9]


def test_daemon_reports_open_connections_and_the_merge(tmp_path):
    """Through the socket, on the sim daemon: ping carries the gauge of
    open client connections, status the merge's counters, and every record
    the program it rode."""
    import tempfile

    from test_call_records import _spawn, _stop, _wait_held

    home = tempfile.mkdtemp(prefix="cm-")
    sock = os.path.join(home, "sim.sock")
    proc = _spawn(sock, {"TENDERMINT_DEVD_SIM_RATE": "2000"})
    client = devd.DevdClient(sock)
    try:
        _wait_held(client, proc, 30.0)
        others = [devd.DevdClient(sock) for _ in range(4)]
        done = []
        ts = [threading.Thread(
            target=lambda c=c: done.append(c.verify_batch(_items(40, b"m"))))
            for c in others]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert len(done) == 4 and all(d == [True] * 40 for d in done)
        assert client.ping()["conns_open"] >= 5
        st = client.status()
        assert st["merge"]["requests"] == 4 and st["merge"]["lanes"] == 160
        assert 1 <= st["merge"]["programs"] <= 4
        assert st["conns_open_max"] >= 5
        rep = client.spans()
        assert tuple(rep["fields"]) == devd_spans.FIELDS
        rows = [dict(zip(rep["fields"], r)) for r in rep["records"]]
        assert sum(1 for r in rows if r["program"] == r["seq"]) == \
            st["merge"]["programs"]
        for c in others:
            c.close()
    finally:
        _stop(client, proc)
