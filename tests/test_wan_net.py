"""A validator net across datacenters, small: the plain reference
`perfbench/reference/wan_ref.py` on hand-worked cases, and seven
in-process validators in the seven regions of
`perfbench/configs/committee-wan-signedkv.json`, its table scaled down
so that the net runs in seconds, held against that reference: every
link's ping round trip at least the configured one, every height at
least `quorum_floor_ms`, all nodes agree, every commit passes
`commit_ref`. The judge's own functions (`harness/wan_judge.py`) do
the comparing, so they are run here on what a real net gives them.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import commit_ref, wan_ref  # noqa: E402

SCALE = 0.2          # 312 ms becomes 62.4 ms: a height lasts a fifth of a second
HEIGHTS = 6


def _cell_config() -> dict:
    with open(os.path.join(BENCH, "configs", "committee-wan-signedkv.json")) as f:
        return json.load(f)


# -- the reference, by hand -------------------------------------------------------


@pytest.mark.parametrize("n,k", [(3, 3), (4, 3), (7, 5), (15, 11), (16, 11),
                                 (17, 12), (64, 43)])
def test_more_than_two_thirds(n, k):
    # 15: exactly two thirds is 10, and 10 is not more than it; one more
    assert wan_ref.quorum_count(n) == k
    assert 3 * k > 2 * n >= 3 * (k - 1)


def test_three_validators_in_three_regions_by_hand():
    """Every vote is needed (3 of 3). One-way: a-b 10, a-c 20, b-c 30.
    Proposer a: the proposal is at a, b, c at 0, 10, 20; the prevotes
    are complete at a: max(0, 10+10, 20+20) = 40, at b: max(10, 10,
    20+30) = 50, at c: max(20, 10+30, 20) = 40; the precommits at a:
    max(40, 50+10, 40+20) = 60, at b: max(50, 50, 70) = 70, at c:
    max(60, 80, 40) = 80."""
    net = wan_ref.WanNet(["a", "b", "c"], {"a:a": 2, "b:b": 2, "c:c": 2,
                                           "a:b": 20, "a:c": 40, "b:c": 60}, 3)
    assert net.prevote_quorum_ms(0) == [40.0, 50.0, 40.0]
    assert [net.quorum_floor_ms(0, o) for o in range(3)] == [60.0, 70.0, 80.0]
    assert net.link_rtt_ms(1, 2) == 60.0 and net.link_one_way_ms(2, 1) == 30.0
    assert len(net.links()) == 6
    assert (0, 2, 20.0, 40.0) in net.links()


def test_four_validators_in_two_regions_by_hand():
    """Validators 0, 2 in a and 1, 3 in b; 3 of 4 make a quorum; one-way
    1 inside a region, 10 between. Proposer 0: proposal at 0, 10, 1, 10.
    Prevotes reach 0 at 0, 20, 2, 20: the third at 20; reach 1 at 10, 10,
    11, 11: the third at 11; 2 as 0 (1, 20, 1, 20): 20; 3 as 1: 11.
    Precommits reach 0 at 20+0, 11+10, 20+1, 11+10: the third at 21."""
    net = wan_ref.WanNet(["a", "b"], {"a:a": 2, "b:b": 2, "a:b": 20}, 4)
    assert [net.region_of(i) for i in range(4)] == ["a", "b", "a", "b"]
    assert net.prevote_quorum_ms(0) == [20.0, 11.0, 20.0, 11.0]
    assert net.quorum_floor_ms(0, 0) == 21.0
    assert net.link_rtt_ms(0, 2) == 2.0          # two of one region


def test_the_floor_takes_the_shorter_way_round():
    """A table from ping measurements need not obey the triangle
    inequality; gossip relays, so the floor must not assume the direct
    link: a-c is 50 one way direct and 20 through b."""
    net = wan_ref.WanNet(["a", "b", "c"], {"a:a": 2, "b:b": 2, "c:c": 2,
                                           "a:b": 20, "b:c": 20, "a:c": 100}, 3)
    assert net.link_one_way_ms(0, 2) == 50.0     # the link is what it is
    assert net.prevote_quorum_ms(0) == [40.0, 30.0, 20.0]   # c: 10 + 10, not 50
    direct = wan_ref.WanNet(["a", "b", "c"], {"a:a": 2, "b:b": 2, "c:c": 2,
                                              "a:b": 20, "b:c": 20, "a:c": 40}, 3)
    assert net.quorum_floor_ms(0, 2) == direct.quorum_floor_ms(0, 2)


def test_the_reference_refuses_a_table_with_a_hole_or_two_values():
    with pytest.raises(ValueError):
        wan_ref.WanNet(["a", "b"], {"a:a": 1, "a:b": 20}, 4)
    with pytest.raises(ValueError):
        wan_ref.WanNet(["a", "b"], {"a:a": 1, "b:b": 1, "a:b": 20, "b:a": 21}, 4)


def test_the_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(BENCH, "reference", "wan_ref.py")) as f:
        tree = ast.parse(f.read())
    imports = {n.module if isinstance(n, ast.ImportFrom) else a.name
               for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
               for a in n.names}
    assert imports == {"__future__", "itertools"}


def test_the_cells_floor_from_us_east_and_from_sydney():
    """What the issue gives for the 11th of 16 votes: 57 ms seen from
    us-east-1, 129 ms from ap-southeast-2 (one-way legs of the table)."""
    cfg = _cell_config()
    net = wan_ref.WanNet(cfg["regions"], cfg["rtt_ms"], 16)
    eleventh = lambda i: sorted(net._d[j][i] for j in range(16))[10]  # noqa: E731
    assert eleventh(0) == 57.5 and eleventh(5) == 129.0
    floors = [net.quorum_floor_ms(p, 0) for p in range(16)]
    assert 200.0 <= min(floors) and max(floors) <= 260.0


# -- seven validators in seven regions ----------------------------------------------


@pytest.fixture(scope="module")
def wan7(tmp_path_factory):
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.config.toml import ensure_root
    from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
    from tendermint_tpu.node.node import default_new_node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS

    cfg0 = _cell_config()
    regions = cfg0["regions"]
    rtt_ms = {pair: ms * SCALE for pair, ms in cfg0["rtt_ms"].items()}
    table = ",".join(f"{pair}={ms:g}" for pair, ms in rtt_ms.items())
    root = str(tmp_path_factory.mktemp("wan7"))
    chain_id = "wan7"
    pvs = sorted((PrivValidatorFS(gen_priv_key_ed25519(b"wan7-val-%d" % i), None)
                  for i in range(7)), key=lambda pv: pv.get_address())
    genesis = GenesisDoc(
        genesis_time_ns=time.time_ns(), chain_id=chain_id,
        validators=[GenesisValidator(pv.get_pub_key(), 10, f"node{i}")
                    for i, pv in enumerate(pvs)])
    nodes = []
    try:
        for i, pv in enumerate(pvs):
            cfg = test_config()
            ensure_root(os.path.join(root, f"node{i}"), cfg)
            cfg.base.chain_id, cfg.base.moniker = chain_id, f"node{i}"
            cfg.rpc.laddr = cfg.p2p.laddr = "tcp://127.0.0.1:0"
            cfg.p2p.addr_book_strict = False
            cfg.p2p.test_link_region = regions[i % len(regions)]
            cfg.p2p.test_link_rtt_ms = table
            c = cfg.consensus
            # time-outs a round trip fits in, as a deployment's do; a
            # short commit time-out that is still waited out
            c.timeout_propose, c.timeout_prevote, c.timeout_precommit = 2.0, 1.0, 1.0
            c.timeout_commit, c.skip_timeout_commit = 0.05, False
            genesis.save_as(cfg.base.genesis_file())
            pv.file_path = cfg.base.priv_validator_file()
            pv.save()
            node = default_new_node(cfg)
            node.start()
            if nodes:
                node.sw.dial_seeds([
                    f"127.0.0.1:{m.listener.internal_address().port}" for m in nodes])
            nodes.append(node)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and \
                min(n.block_store.height() for n in nodes) < HEIGHTS:
            time.sleep(0.1)
        yield {"nodes": nodes, "regions": regions, "rtt_ms": rtt_ms,
               "chain_id": chain_id}
    finally:
        for node in nodes:
            node.stop()


def _fleet_links(nodes) -> dict:
    """What `committee_wan.fleet_links` reads over the RPC, in-process."""
    out = {}
    for i, node in enumerate(nodes):
        for rec in node.link_records():
            j = int(re.fullmatch(r"node(\d+)", rec["moniker"]).group(1))
            out[(i, j)] = {"rtt": rec["rtt"], "link": rec.get("link")}
    return out


def test_the_net_commits_and_every_node_agrees(wan7):
    nodes = wan7["nodes"]
    assert min(n.block_store.height() for n in nodes) >= HEIGHTS
    for h in range(1, HEIGHTS + 1):
        hashes = {n.block_store.load_block_meta(h).block_id.hash for n in nodes}
        assert len(hashes) == 1, h
    for n in nodes:
        assert n.sw.peers.size() == 6
        assert all(rec["link"]["frames"] > 0 for rec in n.link_records())
    # one timer thread a node, not one a link; none of them a Python thread
    import threading

    python_threads = {t.native_id for t in threading.enumerate()}
    assert "p2p.delayLine" not in [t.name for t in threading.enumerate()]
    names = []
    for task in os.listdir("/proc/self/task"):
        if int(task) not in python_threads:
            with open(f"/proc/self/task/{task}/comm") as f:
                names.append(f.read().strip())
    assert names.count("p2p.delayLine") == 7


def test_every_links_round_trip_is_at_least_the_references(wan7):
    from harness import wan_judge

    net = wan_ref.WanNet(wan7["regions"], wan7["rtt_ms"], 7)
    links = _fleet_links(wan7["nodes"])
    records, missing, under = wan_judge.link_records(net, links)
    assert len(records) == 42 and missing == [] and under == []
    for rec in records:
        assert rec["rtt"]["count"] >= 1
        assert 1000.0 * rec["rtt"]["min_s"] >= rec["configured_rtt_ms"]
        assert 0.0 <= rec["rtt_over_configured_ms"] < 500.0
        assert rec["link"]["region"] == rec["region_to"]
        assert rec["link"]["frames"] > 0 and rec["link"]["late_max_s"] < 0.5
    # the same records with one link's delay off: that link reads under
    i, j = 0, 5
    faulted = dict(links)
    faulted[(i, j)] = {"rtt": {"count": 3, "min_s": 0.0004, "last_s": 0.0005,
                               "smoothed_s": 0.0005},
                       "link": {**links[(i, j)]["link"], "delay_s": 0.0}}
    faulted.pop((3, 4))
    _r, missing, under = wan_judge.link_records(net, faulted)
    assert missing == [(3, 4)] and under == [(i, j)]


def test_no_height_comes_in_under_the_quorum_floor(wan7):
    from harness import wan_judge

    nodes = wan7["nodes"]
    net = wan_ref.WanNet(wan7["regions"], wan7["rtt_ms"], 7)
    per_node = [[t.to_json() for t in n.consensus_state.trace.last(64)]
                for n in nodes]
    for observer in (0, 5):
        records, under = wan_judge.heights(net, per_node, observer)
        assert len(records) >= HEIGHTS - 1 and under == 0
        for rec in records:
            assert rec["measured_ms"] >= rec["floor_ms"] > 20.0
            assert rec["over_floor_ms"] < 2000.0
    # the proposer rotates: more than one floor was put to the test
    assert len({rec["proposer"] for rec in records}) >= 3
    # a net that commits under the floor is caught: the same traces
    # against a table ten times slower
    slow = wan_ref.WanNet(wan7["regions"],
                          {p: 10 * ms for p, ms in wan7["rtt_ms"].items()}, 7)
    _records, under = wan_judge.heights(slow, per_node, 0)
    assert under >= 1
    # what a vote round waits for the net is on every height's trace
    aux = per_node[0][1]["aux"]
    assert aux["prevote_quorum_wait_s"] > 0.005 and aux["precommit_quorum_wait_s"] > 0.005
    assert aux["relay_holds"] >= 1 and aux["relay_hold_s"] > 0
    assert aux["last_commit_precommits"] >= 5


def test_every_commit_passes_the_plain_quorum_reference(wan7):
    from harness import rpc

    node = wan7["nodes"][0]
    addr = ("127.0.0.1", node.rpc_port())
    vals = commit_ref.validator_set(rpc.call(addr, "genesis")["genesis"])
    for h in range(1, HEIGHTS):
        block_id = rpc.call(addr, "block", {"height": h})["block_meta"]["block_id"]
        commit = rpc.call(addr, "commit", {"height": h})["commit"]
        verdict = commit_ref.check_commit(wan7["chain_id"], vals, h, block_id, commit)
        assert verdict["quorum"] and not verdict["refused"], h


def test_the_hold_of_each_peer_follows_that_peers_own_round_trip(wan7):
    """Node 5 (ap-southeast-2) holds a relay to sa-east-1 (312 ms x SCALE)
    longer than one to ap-northeast-1 (108 ms x SCALE): twice each link's
    own round trip or twice the lag its announcements show, never one
    number for all."""
    from tendermint_tpu.consensus.reactor import (
        PEER_STATE_KEY,
        VOTE_RELAY_DELAY_MAX,
        VOTE_RELAY_DELAY_MIN,
    )

    node = wan7["nodes"][5]
    holds = {}
    for peer in node.sw.peers.list():
        ps = peer.get(PEER_STATE_KEY)
        hold = node.consensus_reactor._relay_delay(ps)
        assert VOTE_RELAY_DELAY_MIN <= hold <= VOTE_RELAY_DELAY_MAX
        assert hold >= min(VOTE_RELAY_DELAY_MAX, 2.0 * peer.rtt_s()) - 1e-9
        holds[peer.node_info.moniker] = hold
    # the farther peer's hold is never the shorter (equal where the lag
    # over all peers, the floor under every hold, is the longest term)
    assert holds["node6"] >= holds["node2"]
    assert holds["node6"] >= 2 * 0.312 * SCALE
