"""Upgrade-at-height orchestration (round 22, docs/upgrade.md): the
genesis commit-format schedule, the handshake refusal that keeps
mixed-schedule nets from forking at the flip, the AggregateLastCommit
round-state stand-in, forged/sub-quorum aggregate refusal on every
ingest surface (the shared verify core gossip, fast-sync, statesync and
the light client all call), and — slow tier — a real node SIGKILLed
across the boundary whose WAL replay must re-derive the right commit
format per height."""

import json
import os
import signal

import pytest

from tendermint_tpu.codec.binary import Decoder
from tendermint_tpu.crypto import ed25519_agg
from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
from tendermint_tpu.types.agg_commit import (
    AggregateCommit,
    AggregateLastCommit,
    commit_from_json,
    commit_is_aggregate,
    decode_commit,
)
from tendermint_tpu.types.block import Commit
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.validator_set import CommitError
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT

from consensus_common import free_port, init_node_home, node_proc, rpc, wait_height
from test_types import BLOCK_ID, make_val_set, signed_vote

CHAIN = "test-chain"


def _signed_commit(n=4, height=5, drop=()):
    """A fully-signed precommit Commit over BLOCK_ID; indices in `drop`
    abstain (None precommit)."""
    vs, privs = make_val_set(n)
    pres = []
    for i, pv in enumerate(privs):
        if i in drop:
            pres.append(None)
            continue
        pres.append(signed_vote(pv, vs, height, 0, VOTE_TYPE_PRECOMMIT,
                                BLOCK_ID))
    return vs, Commit(BLOCK_ID, pres), height


# -- the genesis schedule ---------------------------------------------------


class TestGenesisSchedule:
    def _doc(self, **kw):
        pv = gen_priv_key_ed25519(b"genesis-val")
        return GenesisDoc(
            genesis_time_ns=1,
            chain_id="up-chain",
            validators=[GenesisValidator(pv.pub_key(), 10, "v0")],
            **kw,
        )

    def test_flip_below_two_refused(self):
        doc = self._doc(upgrade_height=1, upgrade_format="aggregate")
        with pytest.raises(ValueError, match="upgrade_height must be >= 2"):
            doc.validate_and_complete()

    def test_same_format_flip_refused(self):
        doc = self._doc(upgrade_height=5, upgrade_format="full")
        with pytest.raises(ValueError, match="equals commit_format"):
            doc.validate_and_complete()

    def test_format_without_height_refused(self):
        doc = self._doc(upgrade_format="aggregate")
        with pytest.raises(ValueError, match="without upgrade_height"):
            doc.validate_and_complete()

    def test_unknown_upgrade_format_refused(self):
        doc = self._doc(upgrade_height=5, upgrade_format="zip")
        with pytest.raises(ValueError, match="unknown upgrade_format"):
            doc.validate_and_complete()

    def test_format_at_height_and_schedule_string(self):
        doc = self._doc(upgrade_height=4, upgrade_format="aggregate")
        doc.validate_and_complete()
        assert doc.commit_format_at(3) == "full"
        assert doc.commit_format_at(4) == "aggregate"
        assert doc.commit_format_at(10 ** 9) == "aggregate"
        assert not doc.aggregate_commits_at(3)
        assert doc.aggregate_commits_at(4)
        assert doc.schedule_string() == "full>aggregate@4"
        # no flip scheduled: the format holds forever
        plain = self._doc()
        plain.validate_and_complete()
        assert plain.commit_format_at(10 ** 9) == "full"
        assert plain.schedule_string() == "full"

    def test_schedule_json_round_trip(self):
        doc = self._doc(upgrade_height=7, upgrade_format="aggregate")
        doc.validate_and_complete()
        obj = doc.to_json()
        assert obj["upgrade_height"] == 7
        assert obj["upgrade_format"] == "aggregate"
        back = GenesisDoc.from_json(obj)
        assert back.schedule_string() == doc.schedule_string()
        # an unscheduled doc serializes without the keys (byte-compat
        # with every pre-flag genesis)
        plain = self._doc()
        plain.validate_and_complete()
        assert "upgrade_height" not in plain.to_json()


# -- schedule-gated handshake ----------------------------------------------


def _node_info(seed: bytes, schedule: str | None, network: str = "up-net",
               legacy_format: str | None = None):
    from tendermint_tpu.p2p.node_info import NodeInfo

    other = []
    if schedule is not None:
        other.append(f"commit_schedule={schedule}")
    if legacy_format is not None:
        other.append(f"commit_format={legacy_format}")
    return NodeInfo(gen_priv_key_ed25519(seed).pub_key(), "m", network,
                    "1/test", other=other)


class TestScheduleHandshake:
    def test_same_schedule_compatible(self):
        a = _node_info(b"a", "full>aggregate@100")
        b = _node_info(b"b", "full>aggregate@100")
        assert a.compatible_with(b) is None

    def test_schedule_mismatch_named(self):
        # same format TODAY, different flip height — the disagreement
        # that forks AT the flip, so it must refuse at the handshake
        a = _node_info(b"a", "full>aggregate@100")
        b = _node_info(b"b", "full>aggregate@200")
        reason = a.compatible_with(b)
        assert reason is not None
        assert reason.startswith("commit schedule mismatch")
        assert "full>aggregate@100" in reason

    def test_legacy_format_flag_fallback(self):
        # a round-18 peer advertises only commit_format=; an unscheduled
        # round-22 node reads as schedule "full" and stays compatible
        old = _node_info(b"a", None, legacy_format="full")
        new = _node_info(b"b", "full")
        assert new.compatible_with(old) is None
        flipped = _node_info(b"c", "full>aggregate@4")
        assert flipped.compatible_with(old) is not None


class _FakeStream:
    def close(self):
        pass


class _FakePeer:
    outbound = True

    def __init__(self, info):
        self._info = info
        self.stream = _FakeStream()

    def handshake(self, _our_info):
        return self._info

    def pub_key(self):
        return self._info.pub_key


class TestScheduleRefusedCounter:
    def test_mismatch_counted_as_schedule_refused(self):
        from tendermint_tpu.p2p.switch import Switch

        sw = Switch()
        sw.node_info = _node_info(b"ours", "full>aggregate@4")
        with pytest.raises(ConnectionError, match="commit schedule mismatch"):
            sw.add_peer(_FakePeer(_node_info(b"them", "full")))
        assert sw.adversary["schedule_refused"] == 1
        # a plain network mismatch refuses too but does NOT land in the
        # schedule counter — the operator alarm stays specific
        with pytest.raises(ConnectionError, match="network mismatch"):
            sw.add_peer(_FakePeer(
                _node_info(b"other", "full>aggregate@4", network="else")))
        assert sw.adversary["schedule_refused"] == 1


# -- the AggregateLastCommit stand-in --------------------------------------


class TestAggregateLastCommit:
    def test_stand_in_contract(self):
        vs, commit, height = _signed_commit()
        agg = AggregateCommit.from_commit(commit, CHAIN, vs)
        alc = AggregateLastCommit(agg, vs)
        assert alc.has_two_thirds_majority()
        assert alc.two_thirds_majority() == BLOCK_ID
        assert alc.make_commit() is agg
        assert alc.has_all()
        # vote-gossip must find NO per-vote lane to ship (the reactor's
        # aggregate catchup branch ships the whole commit instead)
        assert alc.bit_array().num_true_bits() == 0
        # but coverage screens still see the signer lanes
        assert alc.get_by_index(0) is not None
        # and late precommits cannot be absorbed
        vote = signed_vote(make_val_set(4)[1][0], vs, height, 0,
                           VOTE_TYPE_PRECOMMIT, BLOCK_ID)
        assert alc.begin_add(vote) is None
        assert alc.add_vote(vote) is False


# -- forged / sub-quorum refusal (the shared ingest core) ------------------


class TestAggregateRefusal:
    def test_sub_quorum_aggregation_refused(self):
        vs, commit, _ = _signed_commit(drop=(2, 3))  # 2 of 4 signed
        with pytest.raises(CommitError, match="only 20/40 power"):
            AggregateCommit.from_commit(commit, CHAIN, vs)

    def test_forged_scalar_refused_everywhere(self):
        vs, commit, height = _signed_commit()
        agg = AggregateCommit.from_commit(commit, CHAIN, vs)
        agg.verify(CHAIN, vs, agg_verifier=ed25519_agg.verify_aggregate)
        forged = AggregateCommit.from_bytes(agg.to_bytes())
        forged.s_agg = bytes(32)
        # the direct verify (what gossip's _screen_agg_commit calls)
        with pytest.raises(CommitError, match="failed verification"):
            forged.verify(CHAIN, vs,
                          agg_verifier=ed25519_agg.verify_aggregate)
        # and the set-level commit verify (fast-sync / statesync /
        # store ingest all route through ValidatorSet.verify_commit)
        with pytest.raises(CommitError):
            vs.verify_commit(CHAIN, BLOCK_ID, height, forged)

    def test_dropped_signer_bit_refused(self):
        vs, commit, _ = _signed_commit()
        agg = AggregateCommit.from_commit(commit, CHAIN, vs)
        tampered = AggregateCommit.from_bytes(agg.to_bytes())
        # claim one fewer signer while keeping the same scalar: the
        # bitmap/nonce invariant trips before any curve math
        tampered.signers.set_index(0, False)
        tampered.rs = tampered.rs[1:]
        with pytest.raises(CommitError):
            tampered.verify(CHAIN, vs,
                            agg_verifier=ed25519_agg.verify_aggregate)

    def test_light_client_aggregate_overlap(self):
        from tendermint_tpu.rpc.light import LightClient, LightClientError

        vs, commit, height = _signed_commit()
        agg = AggregateCommit.from_commit(commit, CHAIN, vs)
        # trusted set IS the signing set: full old-set overlap, accepted
        LightClient(None, CHAIN, vs, height - 1) \
            ._check_old_set_overlap_aggregate(height, agg, vs)
        # a disjoint trusted set gets zero old-power from the bitmap —
        # condition (d) fails even though the aggregate itself verifies
        old_privs = [gen_priv_key_ed25519(f"old-{i}".encode())
                     for i in range(4)]
        from tendermint_tpu.types.validator import Validator
        from tendermint_tpu.types.validator_set import ValidatorSet

        old_set = ValidatorSet(
            [Validator.new(p.pub_key(), 10) for p in old_privs])
        lc = LightClient(None, CHAIN, old_set, height - 1)
        with pytest.raises(LightClientError):
            lc._check_old_set_overlap_aggregate(height, agg, vs)
        # and a forged aggregate never reaches the overlap tally
        forged = AggregateCommit.from_bytes(agg.to_bytes())
        forged.s_agg = bytes(32)
        lc_ok = LightClient(None, CHAIN, vs, height - 1)
        with pytest.raises(LightClientError, match="failed"):
            lc_ok._check_old_set_overlap_aggregate(height, forged, vs)


# -- wire / JSON dispatch ---------------------------------------------------


class TestCommitDispatch:
    def test_decode_commit_schedule_gate(self):
        vs, commit, _ = _signed_commit()
        agg = AggregateCommit.from_commit(commit, CHAIN, vs)
        with pytest.raises(ValueError, match="aggregate commit refused"):
            decode_commit(Decoder(agg.to_bytes()), aggregate_commits=False)
        got = decode_commit(Decoder(agg.to_bytes()), aggregate_commits=True)
        assert commit_is_aggregate(got)
        # full commits pass regardless of the flag (pre-flip blocks are
        # served to post-flip nodes during catchup)
        full = decode_commit(Decoder(commit.to_bytes()),
                             aggregate_commits=True)
        assert not commit_is_aggregate(full)

    def test_commit_from_json_dispatch(self):
        vs, commit, _ = _signed_commit()
        agg = AggregateCommit.from_commit(commit, CHAIN, vs)
        back = commit_from_json(agg.to_json())
        assert commit_is_aggregate(back)
        assert back.to_bytes() == agg.to_bytes()
        full = commit_from_json(commit.to_json())
        assert not commit_is_aggregate(full)
        assert full.to_bytes() == commit.to_bytes()


# -- a live chain across the flip, in process ----------------------------------


def test_live_chain_crosses_the_flip_without_missing_a_height():
    """One validator's real ConsensusState with the flip at H=3: every
    height commits, the blocks below H carry full last-commits and the
    blocks from H on aggregates that verify against the validator set,
    and nothing was refused on the way (the in-process half of the
    rolling-upgrade scenario; the process fleet is ops/localnet's)."""
    from consensus_common import (
        TEST_CHAIN_ID,
        new_consensus_state,
        rand_gen_state,
        wait_for_height,
    )

    state, pvs = rand_gen_state(1)
    state.genesis_doc.upgrade_height = 3
    state.genesis_doc.upgrade_format = "aggregate"
    state.genesis_doc.validate_and_complete()
    cs = new_consensus_state(state, pvs[0])
    cs.start()
    try:
        assert wait_for_height(cs, 7, timeout=60), cs.rs.height
    finally:
        cs.stop()
    for h in range(2, 7):
        lc = cs.block_store.load_block(h).last_commit
        assert commit_is_aggregate(lc) == (h >= 3), f"height {h}"
        if h >= 3:
            lc.verify(TEST_CHAIN_ID, state.validators)
    assert cs.agg_commits_proposed >= 4
    assert cs.agg_commit_rejects == 0


# -- boundary crash / WAL replay (slow tier) --------------------------------


@pytest.mark.slow
def test_upgrade_boundary_crash_replay(tmp_path):
    """SIGKILL a real node right as it crosses the flip, twice, and
    prove replay re-derives the right commit format PER HEIGHT: the WAL
    straddles #ENDHEIGHT around H, the store holds full commits below H
    and aggregates from H on, and the restarted node keeps committing
    aggregates."""
    home = str(tmp_path / "node")
    init_node_home(home, "upgrade-crash-chain")
    gpath = os.path.join(home, "genesis.json")
    with open(gpath) as f:
        g = json.load(f)
    g["upgrade_height"] = 4
    g["upgrade_format"] = "aggregate"
    with open(gpath, "w") as f:
        json.dump(g, f)

    port = free_port()
    p = node_proc(home, port)
    try:
        # cross the flip live, then die mid-era
        assert wait_height(port, 4, 120) >= 4
        p.send_signal(signal.SIGKILL)
        p.wait()
        # replay spans both formats (#ENDHEIGHT entries straddle H)
        p = node_proc(home, port)
        assert wait_height(port, 6, 120) >= 6
        p.send_signal(signal.SIGKILL)
        p.wait()
        # a second replay starts INSIDE the aggregate era
        p = node_proc(home, port)
        assert wait_height(port, 7, 120) >= 7
        below = rpc(port, "block", height=3)["block"]["last_commit"]
        assert "precommits" in below and "s_agg" not in below
        for h in (4, 6):
            lc = rpc(port, "block", height=h)["block"]["last_commit"]
            assert "s_agg" in lc, f"height {h} lost the aggregate format"
    finally:
        p.kill()
        p.wait()
