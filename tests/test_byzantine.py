"""Byzantine fault tolerance test (reference: consensus/byzantine_test.go).

4 validators, 1 byzantine. The byzantine proposer signs TWO conflicting
proposals and sends each to a different subset of peers (bypassing the
double-sign guard, byzantine_test.go:162-220 + ByzantinePrivValidator
268). The three honest validators must still converge: the chain advances
and every honest node commits identical blocks.
"""

from __future__ import annotations

import threading
import time

import pytest

from tendermint_tpu.consensus import messages as msgs
from tendermint_tpu.consensus.reactor import DATA_CHANNEL, ConsensusReactor, _enc
from tendermint_tpu.consensus.state import MsgInfo
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p import make_connected_switches
from tendermint_tpu.p2p.node_info import NodeInfo, default_version
from tendermint_tpu.types import BlockID, Proposal
from tendermint_tpu.types.priv_validator import PrivValidatorFS
from tests.test_reactors import TEST_CHAIN_ID, make_genesis, make_node, wait_until
from tendermint_tpu.config import test_config as _test_config


class ByzantinePrivValidator:
    """Signs anything: no last-height/round/step regression guard
    (byzantine_test.go:268-305)."""

    def __init__(self, inner: PrivValidatorFS):
        self.inner = inner

    def get_address(self) -> bytes:
        return self.inner.get_address()

    def get_pub_key(self):
        return self.inner.get_pub_key()

    def sign_vote(self, chain_id: str, vote):
        vote.signature = self.inner.priv_key.sign(vote.sign_bytes(chain_id))
        return vote

    def sign_proposal(self, chain_id: str, proposal):
        proposal.signature = self.inner.priv_key.sign(proposal.sign_bytes(chain_id))
        return proposal

    def sign_heartbeat(self, chain_id: str, hb):
        hb.signature = self.inner.priv_key.sign(hb.sign_bytes(chain_id))
        return hb


def make_byzantine_decide_proposal(cs, sw):
    """Replace default_decide_proposal: two conflicting blocks, one per
    peer partition (byzantine_test.go:162-220)."""

    def byz_decide(height: int, round_: int) -> None:
        rs = cs.rs
        # two different blocks: created from different mempool views — we
        # fake divergence by tweaking nothing vs injecting a tx
        block_a, parts_a = cs.create_proposal_block()
        cs.mempool.check_tx(b"byz-extra-tx=1")
        block_b, parts_b = cs.create_proposal_block()
        if block_a is None or block_b is None:
            return
        peers = sw.peers.list()
        half = len(peers) // 2
        for block, parts, targets in (
            (block_a, parts_a, peers[:half]),
            (block_b, parts_b, peers[half:]),
        ):
            pol_round, pol_block_id = rs.votes.pol_info()
            proposal = Proposal(
                height=height,
                round_=round_,
                block_parts_header=parts.header(),
                pol_round=pol_round,
                pol_block_id=pol_block_id or BlockID(),
            )
            cs.priv_validator.sign_proposal(cs.state.chain_id, proposal)
            for peer in targets:
                peer.send(DATA_CHANNEL, _enc(msgs.ProposalMessage(proposal)))
                for i in range(parts.total):
                    peer.send(
                        DATA_CHANNEL,
                        _enc(msgs.BlockPartMessage(height, round_, parts.get_part(i))),
                    )
        # the byzantine node itself adopts block_a so it keeps voting
        cs.send_internal_message(MsgInfo(msgs.ProposalMessage(
            cs.priv_validator.sign_proposal(
                cs.state.chain_id,
                Proposal(
                    height=height, round_=round_,
                    block_parts_header=parts_a.header(),
                    pol_round=-1, pol_block_id=BlockID(),
                ),
            )
        )))
        for i in range(parts_a.total):
            cs.send_internal_message(
                MsgInfo(msgs.BlockPartMessage(height, round_, parts_a.get_part(i)))
            )

    return byz_decide


@pytest.mark.slow
def test_byzantine_proposer_cannot_halt_chain():
    doc, pvs = make_genesis(4)
    nodes = [make_node(doc, pvs[i]) for i in range(4)]
    for n in nodes:
        n.subscribe_blocks()
    # find which node is the height-1 proposer; make THAT one byzantine
    proposer_addr = nodes[0].state.validators.get_proposer().address
    byz_idx = next(
        i for i, pv in enumerate(pvs) if pv.get_address() == proposer_addr
    )
    byz_node = nodes[byz_idx]
    byz_node.cs.set_priv_validator(ByzantinePrivValidator(pvs[byz_idx]))

    reactors = []

    def init(i, sw):
        node = nodes[i]
        con_r = ConsensusReactor(node.cs, fast_sync=False)
        con_r.set_event_switch(node.evsw)
        sw.add_reactor("CONSENSUS", con_r)
        sw.add_reactor("MEMPOOL", MempoolReactor(_test_config().mempool, node.mempool))
        sw.set_node_info(
            NodeInfo(
                pub_key=sw.node_priv_key.pub_key(),
                moniker=f"byz{i}",
                network=TEST_CHAIN_ID,
                version=default_version("test"),
            )
        )
        reactors.append(con_r)
        if i == byz_idx:
            node.cs.decide_proposal = make_byzantine_decide_proposal(node.cs, sw)
        return sw

    switches = make_connected_switches(4, init)
    honest = [n for i, n in enumerate(nodes) if i != byz_idx]
    try:
        # the chain must advance despite conflicting proposals
        assert wait_until(
            lambda: all(n.store.height() >= 2 for n in honest), timeout=60
        ), [n.store.height() for n in honest]
        # and all honest nodes agree byte-for-byte
        for h in (1, 2):
            hashes = {n.store.load_block(h).hash() for n in honest}
            assert len(hashes) == 1, f"honest divergence at height {h}"
    finally:
        for sw in switches:
            sw.stop()
        for n in nodes:
            n.evsw.stop()


def test_flooding_peer_cannot_halt_chain():
    """Adversarial liveness: a peer that floods decodable consensus
    messages (valid-shape votes from a non-validator key) at wire rate
    must not stall the honest validators — the bounded peer-message
    enqueue drops excess instead of wedging recv routines
    (consensus/state._enqueue_peer_msg; the pre-fix behavior froze the
    whole multiplexed connection)."""
    from tendermint_tpu.consensus.reactor import (
        DATA_CHANNEL as _DC,
        STATE_CHANNEL,
        VOTE_CHANNEL,
        VOTE_SET_BITS_CHANNEL,
    )
    from tendermint_tpu.p2p import Switch, connect2_switches
    from tendermint_tpu.p2p.conn import ChannelDescriptor
    from tendermint_tpu.p2p.node_info import NodeInfo, default_version
    from tendermint_tpu.p2p.switch import Reactor
    from tendermint_tpu.types import Vote
    from tendermint_tpu.types.vote import VOTE_TYPE_PREVOTE
    from tests.test_reactors import start_consensus_net, stop_net, wait_until

    nodes, switches = start_consensus_net(4)

    from tendermint_tpu.libs.service import BaseService

    class FloodSender(Reactor, BaseService):
        """Speaks the consensus channels but only to inject traffic."""

        def __init__(self):
            BaseService.__init__(self, name="flood")

        def get_channels(self):
            # all four consensus channels: the victim gossips on
            # STATE/DATA too, and an unknown channel drops the peer
            return [
                ChannelDescriptor(id=ch, priority=5, send_queue_capacity=1000)
                for ch in (STATE_CHANNEL, _DC, VOTE_CHANNEL, VOTE_SET_BITS_CHANNEL)
            ]

        def add_peer(self, peer):
            pass

        def remove_peer(self, peer, reason):
            pass

        def receive(self, ch_id, peer, msg_bytes):
            pass

    flood_sw = Switch()
    flood_sw.add_reactor("FLOOD", FloodSender())
    flood_sw.set_node_info(
        NodeInfo(
            pub_key=flood_sw.node_priv_key.pub_key(),
            moniker="flooder",
            network=nodes[0].state.chain_id,
            version=default_version("test"),
        )
    )
    flood_sw.start()
    try:
        assert wait_until(lambda: all(len(n.blocks) >= 1 for n in nodes),
                          timeout=60)
        # CALIBRATE to the box's current headroom (round-3 flake: this
        # test fails at the tail of a 5-minute suite run on a 1-core box
        # but passes alone — wall-clock deadlines don't transfer across
        # load). Time an UNflooded 2-block stretch now, with whatever
        # leftover suite threads are churning, and scale both the flood
        # pacing and the flooded deadline from it.
        calib_start = min(len(n.blocks) for n in nodes)
        t0 = time.time()
        assert wait_until(
            lambda: all(len(n.blocks) >= calib_start + 2 for n in nodes),
            timeout=180,
        ), "calibration: chain not advancing even without flood"
        t_two_blocks = max(time.time() - t0, 1.0)

        connect2_switches(switches + [flood_sw], 0, 4)
        victim_peer = next(iter(flood_sw.peers.list()), None)
        assert victim_peer is not None

        # flood: shape-valid votes signed by a NON-validator, pinned to
        # the height at flood start (stale as the chain advances — still
        # decodable, still enqueued, still rejected by processing)
        from tendermint_tpu.crypto.keys import gen_priv_key_ed25519

        atk = PrivValidatorFS(gen_priv_key_ed25519(), None)
        flood_height = nodes[0].cs.get_round_state().height  # pin once:
        # the live RoundState mutates under us from the consensus thread
        stop_flood = threading.Event()
        stats = {"sent": 0}

        # pace inversely to headroom: ~200 msg/s on an idle box, scaled
        # down when the calibration says the box is already saturated (an
        # unthrottled python sign+send loop starves the validators of the
        # GIL and stalls consensus by resource exhaustion — which is not
        # the property under test; the bounded enqueue keeping recv
        # routines un-wedged is)
        pace = 0.005 * max(1.0, t_two_blocks / 10.0)

        def flood():
            i = 0
            while not stop_flood.is_set():
                v = Vote(
                    validator_address=atk.get_address(),
                    validator_index=i % 4,
                    height=flood_height,
                    round_=0,
                    type_=VOTE_TYPE_PREVOTE,
                    block_id=BlockID(),
                )
                v = atk.sign_vote(nodes[0].state.chain_id, v)  # returns the
                # signed copy; Vote is not mutated in place
                if victim_peer.try_send(VOTE_CHANNEL, _enc(msgs.VoteMessage(v))):
                    stats["sent"] += 1
                i += 1
                time.sleep(pace)

        flooder = threading.Thread(target=flood, daemon=True)
        flooder.start()

        # the chain must keep committing WHILE being flooded; the deadline
        # scales with the measured unflooded rate (8x headroom: flood
        # processing + drops legitimately slow the chain, they must not
        # STOP it)
        start = min(len(n.blocks) for n in nodes)
        deadline = min(300.0, max(60.0, 8.0 * t_two_blocks))
        # (and the flood has to have been one: a chain that commits two
        # blocks inside a tenth of a second otherwise ends the wait before
        # the paced sender has sent its twenty)
        ok = wait_until(
            lambda: stats["sent"] > 20
            and all(len(n.blocks) >= start + 2 for n in nodes),
            timeout=deadline,
        )
        stop_flood.set()
        flooder.join(5)
        drops = [n.cs._peer_msg_drops for n in nodes]
        assert stats["sent"] > 20, f"flood only delivered {stats['sent']}"
        assert ok, (
            f"chain stalled under flood: blocks={[len(n.blocks) for n in nodes]} "
            f"start={start} deadline={deadline:.0f}s (unflooded 2 blocks took "
            f"{t_two_blocks:.1f}s) flood_sent={stats['sent']} "
            f"ingress_drops={drops} (drops>0 means the bound worked and the "
            f"stall is resource starvation, not a wedged recv routine)"
        )
        # and the victim still has its honest peers
        assert switches[0].peers.size() >= 3
    finally:
        flood_sw.stop()
        stop_net(nodes, switches)
