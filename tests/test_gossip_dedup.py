"""Has-vote-aware gossip dedup (round 20, docs/localnet.md).

The 2NxN redundancy mechanism: every validator's vote reaches every
node ~2N times because the pick/send loops only learn what a peer
holds from votes WE sent it or full VoteSetBits exchanges — the cheap
HasVote announcements peers broadcast after every accepted vote were
mostly dropped on the floor (no tracking array ensured yet, or the
peer had just committed and its announcements were one height "behind"
the mirror). With `consensus.gossip_dedup` on (the default), the STATE
channel feeds all of them into the mirror and the part-set gossip
gains the same screen (HasBlockPartMessage).

These are the unit halves; the process-scale scenario is
ops/localnet.py's (tests/test_localnet.py, slow-marked)."""

from __future__ import annotations

import pytest

from tendermint_tpu.consensus import messages as msgs
from tendermint_tpu.consensus.reactor import (
    PEER_STATE_KEY,
    STATE_CHANNEL,
    ConsensusReactor,
    PeerState,
    _enc,
)
from tendermint_tpu.libs.bitarray import BitArray
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE


class _VoteSet:
    """Minimal vote_set: holds the given indices at (height, round, type)."""

    def __init__(self, height, round_, type_, indices, size=4):
        self.height, self.round_, self.type_ = height, round_, type_
        self._indices = list(indices)
        self._size = size

    def size(self):
        return self._size

    def bit_array(self):
        return BitArray.from_indices(self._size, self._indices)

    def get_by_index(self, index):
        assert index in self._indices
        return ("vote", index)


def test_has_vote_announcement_suppresses_pick():
    """The core dedup claim: a HasVote announcement alone (no vote
    round-trip) must stop the picker from pushing that vote to the
    announcing peer."""
    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 5, 0
    ps.ensure_vote_bit_arrays(5, 4)
    vs = _VoteSet(5, 0, VOTE_TYPE_PREVOTE, [1, 2])

    assert ps.apply_has_vote(msgs.HasVoteMessage(5, 0, VOTE_TYPE_PREVOTE, 1))
    # index 1 is now known-held: only index 2 remains pickable
    for _ in range(8):
        vote = ps.pick_vote_to_send(vs)
        assert vote == ("vote", 2)

    assert ps.apply_has_vote(msgs.HasVoteMessage(5, 0, VOTE_TYPE_PREVOTE, 2))
    assert ps.pick_vote_to_send(vs) is None


def test_has_vote_mid_pick_race_is_benign():
    """A HasVote landing BETWEEN pick and send is the unavoidable race
    (the peer's announcement and our push cross on the wire). The send
    still goes out — one harmless duplicate — but the bit the HasVote
    set must survive the send's own marking, even a FAILED send: a
    failed send leaves the bit as the HasVote left it (held), so the
    picker doesn't re-push a vote the peer itself told us it has."""

    class _Peer:
        def __init__(self, ok):
            self.ok = ok

        def try_send(self, ch, raw):
            return self.ok

    class _Vote:
        height, round_, type_, validator_index = 5, 0, VOTE_TYPE_PREVOTE, 1

        def to_json(self):
            return {"height": self.height}

    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 5, 0
    ps.ensure_vote_bit_arrays(5, 4)
    vs = _VoteSet(5, 0, VOTE_TYPE_PREVOTE, [1])

    picked = ps.pick_vote_to_send(vs)
    assert picked == ("vote", 1)
    # the race: the peer announces the same vote before our send lands
    assert ps.apply_has_vote(msgs.HasVoteMessage(5, 0, VOTE_TYPE_PREVOTE, 1))
    # failed send: pre-round-20 semantics would retry the vote forever
    # (bit only ever set on successful send) — the announcement must win
    assert not ConsensusReactor._send_vote(None, _Peer(ok=False), ps, _Vote())
    assert ps.pick_vote_to_send(vs) is None, (
        "a vote the peer announced must stay unpickable after a failed send"
    )
    # and a successful send re-marking the same bit is idempotent
    assert ConsensusReactor._send_vote(None, _Peer(ok=True), ps, _Vote())
    assert ps.pick_vote_to_send(vs) is None


def test_last_commit_has_vote_lands_only_with_dedup():
    """A node that just committed H keeps broadcasting HasVotes for its
    H-precommits while peers' mirrors already show it at H+1. The
    strict gate (pre-round-20) dropped ALL of them — so everyone kept
    re-pushing commit votes the node already held. With
    allow_last_commit the announcement routes into the last_commit
    tracking array."""
    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 6, 0
    ps.prs.last_commit_round = 0
    ps.ensure_vote_bit_arrays(5, 4)  # height+1 branch -> last_commit array
    announce = msgs.HasVoteMessage(5, 0, VOTE_TYPE_PRECOMMIT, 2)

    assert not ps.apply_has_vote(announce)  # strict gate: dropped
    assert ps.apply_has_vote(announce, allow_last_commit=True)

    # the last-commit picker now skips the announced vote
    last = _VoteSet(5, 0, VOTE_TYPE_PRECOMMIT, [2, 3])
    assert ps.pick_vote_to_send(last) == ("vote", 3)


def _has_votes(height, round_, type_, indices, size=4):
    return msgs.HasVotesMessage(
        height, round_, type_, BitArray.from_indices(size, indices))


def test_has_votes_array_ors_into_the_mirror_and_clears_nothing():
    """The burst form means HasVote for every set bit: the array is
    ORed in. A later array with FEWER bits (each announcement holds only
    what entered since the last) takes nothing back, unlike VoteSetBits,
    which replaces the mirror."""
    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 5, 0
    ps.ensure_vote_bit_arrays(5, 4)
    ps.set_has_vote(5, 0, VOTE_TYPE_PREVOTE, 2)  # a vote we sent it

    assert ps.apply_has_votes(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [0, 2])) == (2, [0])
    assert ps.apply_has_votes(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [1])) == (1, [1])
    assert ps.prs.prevotes.indices() == [0, 1, 2]
    assert ps.prs.precommits.is_empty()  # the other type's array: untouched
    # a repeat lands and tells nothing new
    assert ps.apply_has_votes(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [1])) == (1, [])
    vs = _VoteSet(5, 0, VOTE_TYPE_PREVOTE, [0, 1, 2, 3])
    assert ps.pick_vote_to_send(vs) == ("vote", 3)
    # coordinates no array tracks: dropped, as the single form is
    assert ps.apply_has_votes(_has_votes(5, 1, VOTE_TYPE_PREVOTE, [3])) == (0, [])
    assert ps.apply_has_votes(_has_votes(4, 0, VOTE_TYPE_PREVOTE, [3])) == (0, [])
    assert ps.prs.prevotes.indices() == [0, 1, 2]


def test_last_commit_has_votes_land_only_with_dedup():
    """The height-1 precommit array of a node that just committed routes
    to the last_commit array, and only with allow_last_commit: the gate
    and the routing of the single form."""
    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 6, 0
    ps.prs.last_commit_round = 0
    ps.ensure_vote_bit_arrays(5, 4)  # height+1 branch -> last_commit array
    ps.ensure_vote_bit_arrays(6, 4)
    announce = _has_votes(5, 0, VOTE_TYPE_PRECOMMIT, [0, 2])

    assert ps.apply_has_votes(announce) == (0, [])  # strict gate: dropped
    assert ps.prs.last_commit.is_empty()
    assert ps.apply_has_votes(announce, allow_last_commit=True) == (2, [0, 2])
    assert ps.prs.last_commit.indices() == [0, 2]
    assert ps.prs.precommits.is_empty()  # not the current height's array
    # a height-1 PREVOTE array has no last-commit array to land in
    assert ps.apply_has_votes(
        _has_votes(5, 0, VOTE_TYPE_PREVOTE, [1]), allow_last_commit=True
    ) == (0, [])
    last = _VoteSet(5, 0, VOTE_TYPE_PRECOMMIT, [2, 3])
    assert ps.pick_vote_to_send(last) == ("vote", 3)


def test_laggard_catchup_branch_unaffected_by_dedup():
    """The stored-commit catchup path (peer >= 2 heights behind) must
    keep working under dedup: HasVotes from the laggard for its OWN
    height route into the catchup-commit array (so we skip what it
    has), and announcements for coordinates no array tracks are
    DROPPED, never mis-filed into a same-index bit of another round."""
    ps = PeerState(peer=None)
    ps.prs.height, ps.prs.round_ = 5, 2  # laggard raced past commit round 0
    ps.ensure_vote_bit_arrays(5, 4)

    # an announcement for the untracked commit round is dropped...
    stray = msgs.HasVoteMessage(5, 0, VOTE_TYPE_PRECOMMIT, 1)
    assert not ps.apply_has_vote(stray, allow_last_commit=True)
    # ...and did not leak into the round-2 precommit array
    assert ps.prs.precommits.is_empty()

    # the catchup branch then ensures the commit-round array; the same
    # announcement now lands there and the commit picker skips it
    ps.ensure_catchup_commit_round(5, 0, 4)
    assert ps.apply_has_vote(stray, allow_last_commit=True)
    commit_votes = _VoteSet(5, 0, VOTE_TYPE_PRECOMMIT, [1, 3])
    assert ps.pick_vote_to_send(commit_votes) == ("vote", 3)


# -- the reactor's STATE-channel wiring ---------------------------------------


class _Validators:
    def __init__(self, n):
        self._n = n

    def size(self):
        return self._n


class _RoundState:
    def __init__(self, height, n=4):
        self.height = height
        self.validators = _Validators(n)
        self.last_commit = _Validators(n)  # only size() is consulted


class _ConState:
    def __init__(self, height=5, gossip_dedup=True):
        from types import SimpleNamespace

        self.config = SimpleNamespace(gossip_dedup=gossip_dedup)
        self._rs = _RoundState(height)
        self.vote_recv_mono = {}

    def get_round_state(self):
        return self._rs


class _StubPeer:
    def __init__(self):
        self._kv = {}

    def id(self):
        return "stub-peer-0000"

    def get(self, k):
        return self._kv.get(k)

    def set(self, k, v):
        self._kv[k] = v

    def send(self, ch, raw):
        return True

    def try_send(self, ch, raw):
        return True


def _reactor_with_peer(gossip_dedup: bool):
    r = ConsensusReactor(_ConState(height=5, gossip_dedup=gossip_dedup))
    r._started = True  # receive() guards on is_running()
    peer = _StubPeer()
    ps = PeerState(peer)
    ps.prs.height, ps.prs.round_ = 5, 0
    peer.set(PEER_STATE_KEY, ps)
    return r, peer, ps


def test_state_channel_has_vote_ensures_arrays_when_dedup_on():
    """The first-window drop: at a fresh height the mirror has NO bit
    arrays yet, so every early HasVote used to vanish into the
    set_has_vote no-op. With dedup on, receive() ensures the arrays
    (exactly like the VOTE channel does) before applying."""
    r, peer, ps = _reactor_with_peer(gossip_dedup=True)
    assert ps.prs.prevotes is None  # fresh mirror, nothing ensured
    raw = _enc(msgs.HasVoteMessage(5, 0, VOTE_TYPE_PREVOTE, 2))
    r.receive(STATE_CHANNEL, peer, raw)
    assert r.has_votes_applied == 1
    assert ps.pick_vote_to_send(_VoteSet(5, 0, VOTE_TYPE_PREVOTE, [2])) is None


def test_state_channel_has_vote_dropped_when_dedup_off():
    """gossip_dedup=false restores the pre-round-20 gossip exactly —
    the A/B baseline the bench compares against."""
    r, peer, ps = _reactor_with_peer(gossip_dedup=False)
    raw = _enc(msgs.HasVoteMessage(5, 0, VOTE_TYPE_PREVOTE, 2))
    r.receive(STATE_CHANNEL, peer, raw)
    assert r.has_votes_applied == 0
    assert ps.prs.prevotes is None  # no arrays ensured, announcement lost


@pytest.mark.parametrize("dedup,applied", [(True, 2), (False, 0)])
def test_state_channel_has_votes_ensures_arrays_as_has_vote_does(dedup, applied):
    """The burst form rides the single form's wiring: with dedup on the
    arrays are ensured first and has_votes_applied counts the BITS that
    landed; off, a fresh mirror has no array and the announcement is
    lost, as before round 20."""
    r, peer, ps = _reactor_with_peer(gossip_dedup=dedup)
    r.receive(STATE_CHANNEL, peer, _enc(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [1, 2])))
    assert r.has_votes_applied == applied
    if dedup:
        assert ps.pick_vote_to_send(_VoteSet(5, 0, VOTE_TYPE_PREVOTE, [1, 2])) is None
    else:
        assert ps.prs.prevotes is None


def test_wrong_sized_has_votes_array_is_ignored_without_a_peer_error():
    """An array of another size than the mirror's (a peer at another
    validator set, or one that lies) marks nothing and is no reason to
    drop the peer: the votes still dedup the hard way."""

    class _Switch:
        stopped: list = []

        def stop_peer_for_error(self, peer, reason):
            self.stopped.append(reason)

    r, peer, ps = _reactor_with_peer(gossip_dedup=True)
    r.switch = _Switch()
    for size in (3, 5, 0):
        r.receive(STATE_CHANNEL, peer, _enc(
            _has_votes(5, 0, VOTE_TYPE_PREVOTE, range(size), size=size)))
    assert r.has_votes_applied == 0
    assert ps.prs.prevotes.is_empty() and ps.prs.prevotes.size == 4
    assert r.switch.stopped == []
    assert ps.has_vote_lag is None and r._has_vote_lag is None
    # the right size still lands afterwards
    r.receive(STATE_CHANNEL, peer, _enc(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [3])))
    assert r.has_votes_applied == 1 and ps.prs.prevotes.indices() == [3]


def test_has_vote_lag_takes_one_sample_a_message():
    """One announcement, one sample of the lag the relay hold follows:
    the OLDEST receipt among the bits the message newly set (what the
    hold has to outlast), not one a bit, and none from a bit the mirror
    held already."""
    import time as _time

    r, peer, ps = _reactor_with_peer(gossip_dedup=True)
    ps.ensure_vote_bit_arrays(5, 4)
    ps.set_has_vote(5, 0, VOTE_TYPE_PREVOTE, 3)
    now = _time.monotonic()
    for index, age in ((1, 3.0), (2, 1.0), (3, 50.0)):
        r.con_s.vote_recv_mono[(5, 0, VOTE_TYPE_PREVOTE, index)] = now - age

    r.receive(STATE_CHANNEL, peer, _enc(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [1, 2, 3])))
    assert r.has_votes_applied == 3
    assert ps.has_vote_lag == pytest.approx(3.0, abs=0.5)  # first sample: as read
    # nothing newly set, or a vote we never received from a peer: no sample
    lag = ps.has_vote_lag
    r.receive(STATE_CHANNEL, peer, _enc(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [1, 2])))
    r.receive(STATE_CHANNEL, peer, _enc(_has_votes(5, 0, VOTE_TYPE_PREVOTE, [0])))
    assert ps.has_vote_lag == lag
    # the next sample moves the average by a tenth, once
    r.con_s.vote_recv_mono[(5, 0, VOTE_TYPE_PRECOMMIT, 0)] = _time.monotonic() - 13.0
    r.con_s.vote_recv_mono[(5, 0, VOTE_TYPE_PRECOMMIT, 1)] = _time.monotonic() - 13.0
    r.receive(STATE_CHANNEL, peer, _enc(_has_votes(5, 0, VOTE_TYPE_PRECOMMIT, [0, 1])))
    assert ps.has_vote_lag == pytest.approx(0.9 * lag + 0.1 * 13.0, abs=0.2)
    assert r._has_vote_lag == pytest.approx(ps.has_vote_lag)   # one peer: the same


def test_pending_bits_are_announced_exactly_once_under_contention(monkeypatch):
    """The pending set is shared by the thread that adds votes, the
    timer and the step broadcasts that flush it: with more threads than
    cores and a short switch interval, every vote noted is announced in
    exactly one message (a lost update would drop a bit, a flush that
    raced another would send one twice)."""
    import sys
    import threading
    from types import SimpleNamespace

    from tendermint_tpu.consensus import reactor as reactor_mod

    monkeypatch.setattr(reactor_mod, "VOTE_RELAY_DELAY_MIN", 0.001)
    n, rounds, workers = 1024, 4, 16
    r = ConsensusReactor(_ConState(height=5))
    r.con_s._rs = _RoundState(5, n=n)
    sent: list = []
    r.switch = SimpleNamespace(broadcast=lambda ch, raw: sent.append(raw))
    flushing, done = threading.Event(), threading.Event()

    def note(worker: int) -> None:
        flushing.wait(30)
        for index in range(worker, n, workers):
            for round_ in range(rounds):
                r._note_has_vote(SimpleNamespace(
                    height=5, round_=round_, type_=VOTE_TYPE_PREVOTE,
                    validator_index=index))
            # as the consensus thread does before a step, beside the
            # timer's flushes and the other thread's
            r._flush_has_votes()

    def flush_as_a_step_does() -> None:
        while not done.is_set():
            r._flush_has_votes()
            flushing.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        flusher = threading.Thread(target=flush_as_a_step_does)
        noters = [threading.Thread(target=note, args=(w,)) for w in range(workers)]
        flusher.start()
        for t in noters:
            t.start()
        for t in noters:
            t.join(30)
        done.set()
        flusher.join(30)
        assert not flusher.is_alive() and not any(t.is_alive() for t in noters)
    finally:
        sys.setswitchinterval(old)
        r._wakes.stop()
    r._flush_has_votes()
    import json

    seen = {round_: [] for round_ in range(rounds)}
    for raw in sent:
        m = msgs.msg_from_json(json.loads(raw.decode()))
        assert (m.height, m.type_, m.votes.size) == (5, VOTE_TYPE_PREVOTE, n)
        seen[m.round_].extend(m.votes.indices())
    for round_ in range(rounds):
        assert sorted(seen[round_]) == list(range(n)), round_
    assert r.gossip_announce_bits == n * rounds


def test_has_block_part_announcement_marks_mirror():
    """HasBlockPartMessage on the STATE channel marks the peer's
    part-set mirror so gossip_data stops pushing a part the peer
    already assembled — applied regardless of our own knob (free
    information, only ever reduces redundant sends)."""
    r, peer, ps = _reactor_with_peer(gossip_dedup=False)
    ps.set_has_proposal(
        type(
            "P",
            (),
            {
                "height": 5,
                "round_": 0,
                "block_parts_header": type(
                    "H", (), {"total": 4, "hash": b"x"}
                )(),
                "pol_round": -1,
            },
        )()
    )
    assert not ps.prs.proposal_block_parts.get_index(3)
    r.receive(STATE_CHANNEL, peer, _enc(msgs.HasBlockPartMessage(5, 0, 3)))
    assert r.part_announces_applied == 1
    assert ps.prs.proposal_block_parts.get_index(3)


def test_broadcast_has_part_gated_by_knob():
    """Local part adds only announce when the knob is on (the off arm
    of the A/B must not emit round-20 messages at all)."""
    from tendermint_tpu.types.events import EventDataBlockPart

    sent = []

    class _Switch:
        def broadcast(self, ch, raw):
            sent.append((ch, raw))

    data = EventDataBlockPart(height=5, round_=0, index=1)

    r_off = ConsensusReactor(_ConState(gossip_dedup=False))
    r_off.switch = _Switch()
    r_off._broadcast_has_part(data)
    assert not sent and r_off.part_announces_sent == 0

    r_on = ConsensusReactor(_ConState(gossip_dedup=True))
    r_on.switch = _Switch()
    r_on._broadcast_has_part(data)
    assert len(sent) == 1 and sent[0][0] == STATE_CHANNEL
    assert r_on.part_announces_sent == 1
    msg = msgs.msg_from_json(__import__("json").loads(sent[0][1].decode()))
    assert isinstance(msg, msgs.HasBlockPartMessage)
    assert (msg.height, msg.round_, msg.index) == (5, 0, 1)


def test_relay_screen_holds_fresh_votes_only():
    """The lazy-relay screen: a vote we received under VOTE_RELAY_DELAY
    ago is held (its origin is fanning it out and HasVotes are in
    flight); after the hold, or for unstamped votes (our own,
    store-backed catchup commits), relay is immediate. Off-knob nets
    never hold."""
    import time as _time

    from tendermint_tpu.consensus.reactor import VOTE_RELAY_DELAY

    class _V:
        height, round_, type_, validator_index = 5, 0, VOTE_TYPE_PREVOTE, 1

    r, _peer, ps = _reactor_with_peer(gossip_dedup=True)
    delay = r._relay_delay(ps)
    assert delay == VOTE_RELAY_DELAY  # a peer that measures no round trip
    assert r._relay_hold(_V(), delay) == 0.0  # unstamped: our own vote

    key = (5, 0, VOTE_TYPE_PREVOTE, 1)
    r.con_s.vote_recv_mono[key] = _time.monotonic()
    assert 0.0 < r._relay_hold(_V(), delay) <= VOTE_RELAY_DELAY  # just received: held
    r.con_s.vote_recv_mono[key] = _time.monotonic() - VOTE_RELAY_DELAY - 0.01
    assert r._relay_hold(_V(), delay) == 0.0  # hold expired: genuinely needed

    r_off = ConsensusReactor(_ConState(gossip_dedup=False))
    r_off.con_s.vote_recv_mono[key] = _time.monotonic()
    assert r_off._relay_hold(_V(), delay) == 0.0  # pre-round-20 gossip: no hold


def test_adaptive_relay_delay_clamp_and_fallback():
    """Round 21 satellite: the lazy-relay hold tracks 2x the smoothed
    peer RTT, clamped to [half the constant, one second: above twice the
    longest round trip between two regions, round 32]; no samples keeps
    the constant exactly."""
    from tendermint_tpu.consensus.reactor import (
        VOTE_RELAY_DELAY,
        VOTE_RELAY_DELAY_MAX,
        VOTE_RELAY_DELAY_MIN,
        adaptive_relay_delay,
    )

    assert VOTE_RELAY_DELAY_MIN == pytest.approx(0.5 * VOTE_RELAY_DELAY)
    assert VOTE_RELAY_DELAY_MAX == 1.0 > 2 * 0.312
    # no samples: the constant, byte-for-byte
    assert adaptive_relay_delay(None) == VOTE_RELAY_DELAY
    # fast LAN: clamps at the floor, never disables the hold
    assert adaptive_relay_delay(0.0005) == VOTE_RELAY_DELAY_MIN
    assert adaptive_relay_delay(0.0) == VOTE_RELAY_DELAY_MIN
    # mid-range: tracks 2x RTT
    assert adaptive_relay_delay(0.08) == pytest.approx(0.16)
    # the longest link of the seven-datacenter net: not clamped
    assert adaptive_relay_delay(0.312) == pytest.approx(0.624)
    # garbage sample: clamps at the ceiling
    assert adaptive_relay_delay(1.5) == VOTE_RELAY_DELAY_MAX


def test_reactor_relay_delay_follows_each_peers_own_rtt():
    """The reactor's hold is per peer: constant for a peer with no
    sample, RTT-adaptive from THAT link's ping samples (fed by
    PeerConnMetrics.pong_received) once it has one; a far peer's samples
    move a near peer's hold by nothing, and the lag a peer's
    announcements show raises its own hold alone."""
    from tendermint_tpu.consensus.reactor import (
        VOTE_RELAY_DELAY,
        VOTE_RELAY_DELAY_MAX,
        VOTE_RELAY_DELAY_MIN,
    )
    from tendermint_tpu.p2p.telemetry import PeerRtt

    class _RttPeer(_StubPeer):
        def __init__(self):
            super().__init__()
            self.rtt = PeerRtt()

        def rtt_s(self):
            return self.rtt.value()

    r = ConsensusReactor(_ConState(gossip_dedup=True))
    near, far, blind = PeerState(_RttPeer()), PeerState(_RttPeer()), PeerState(_StubPeer())
    for ps in (near, far, blind):
        assert r._relay_delay(ps) == VOTE_RELAY_DELAY  # no sample yet
    near.peer.rtt.observe(0.001)
    far.peer.rtt.observe(0.312)
    assert r._relay_delay(near) == VOTE_RELAY_DELAY_MIN
    assert r._relay_delay(far) == pytest.approx(0.624)
    assert r._relay_delay(blind) == VOTE_RELAY_DELAY
    # EWMA moves with new samples, and the clamp still rules
    for _ in range(64):
        far.peer.rtt.observe(5.0)
    assert r._relay_delay(far) == VOTE_RELAY_DELAY_MAX
    assert r._relay_delay(near) == VOTE_RELAY_DELAY_MIN
    # a peer whose announcements lag: twice the lag, its own hold alone
    near.has_vote_lag = 0.2
    assert r._relay_delay(near) == pytest.approx(0.4)
    assert r._relay_delay(blind) == VOTE_RELAY_DELAY
    # the lag over all peers is a floor under every peer's hold (one
    # host: a peer's own average says little of its next announcement)
    r._has_vote_lag = 0.15
    assert r._relay_delay(blind) == pytest.approx(0.3)
    assert r._relay_delay(near) == pytest.approx(0.4)     # its own is longer
    assert r._relay_delay(far) == VOTE_RELAY_DELAY_MAX


def test_peer_rtt_smoothing_and_record():
    from tendermint_tpu.p2p.telemetry import PeerRtt

    e = PeerRtt()
    assert e.value() is None and e.record()["count"] == 0
    e.observe(0.1)
    assert e.value() == pytest.approx(0.1)  # first sample seeds exactly
    e.observe(0.2)
    assert e.value() == pytest.approx(0.1 + 0.2 * (0.2 - 0.1))
    e.observe(0.05)
    rec = e.record()
    assert (rec["count"], rec["min_s"], rec["last_s"]) == (3, 0.05, 0.05)


def test_vote_recv_stamp_is_bounded():
    """The stamp map self-prunes on overflow — entries only matter for
    one gossip tick, so unbounded growth would be a leak, not memory."""
    import time as _time

    from tendermint_tpu.consensus.state import ConsensusState

    class _S:
        vote_recv_mono: dict = {}

    stamp = ConsensusState._stamp_vote_recv
    s = _S()

    class _V:
        def __init__(self, h):
            self.height, self.round_ = h, 0
            self.type_, self.validator_index = VOTE_TYPE_PREVOTE, h % 100

    for h in range(4096):
        stamp(s, _V(h))
    assert len(s.vote_recv_mono) == 4096
    # age everything out, then one more stamp triggers the sweep
    for k in list(s.vote_recv_mono):
        s.vote_recv_mono[k] = _time.monotonic() - 10.0
    stamp(s, _V(5000))
    assert len(s.vote_recv_mono) == 1


# -- duplicate-ratio direction ------------------------------------------------


def test_announcements_reduce_redundant_sends_across_peer_fan_out():
    """The ratio direction, deterministically: one vote, three peers.
    Without announcements every peer gets a push (3 sends, 2 of which
    the receiving side would count as duplicates once the vote has
    propagated); with HasVotes applied from two peers, only the silent
    one is picked for — redundant sends drop 3 -> 1. This is the causal
    core of the duplicate-ratio drop."""
    vs = _VoteSet(5, 0, VOTE_TYPE_PREVOTE, [1])

    def fresh_peer():
        ps = PeerState(peer=None)
        ps.prs.height, ps.prs.round_ = 5, 0
        ps.ensure_vote_bit_arrays(5, 4)
        return ps

    peers = [fresh_peer() for _ in range(3)]
    assert sum(ps.pick_vote_to_send(vs) is not None for ps in peers) == 3

    announce = msgs.HasVoteMessage(5, 0, VOTE_TYPE_PREVOTE, 1)
    assert peers[0].apply_has_vote(announce)
    assert peers[1].apply_has_vote(announce)
    picked = [ps.pick_vote_to_send(vs) is not None for ps in peers]
    assert picked == [False, False, True]


@pytest.mark.slow
def test_duplicate_ratio_counters_move_on_live_net(tmp_path):
    """The PR-17 counters and the round-20 dedup counters all move in
    their right directions on a live 4-node real-TCP net with dedup on:
    votes are accepted, the 2NxN redundancy registers as duplicates
    (never negative, never counted as accepts), the ratio is finite,
    and the dedup plumbing demonstrably engages (announcements applied,
    part screens sent AND applied). The wall-clock on-vs-off ratio drop
    is not asserted here: at 4 in-process nodes under one GIL the
    scheduler noise swamps the few-percent gain."""
    from tests.netchaos_common import ChaosNet

    net = ChaosNet(4, str(tmp_path / "dedup-on"), gossip_dedup=True)
    net.start()
    try:
        assert net.wait_height(6, timeout=150), net.heights()
        dups = sum(n.consensus_state.vote_duplicates for n in net.nodes)
        acc = sum(n.consensus_state.vote_accepted for n in net.nodes)
        applied = sum(n.consensus_reactor.has_votes_applied for n in net.nodes)
        part_sent = sum(
            n.consensus_reactor.part_announces_sent for n in net.nodes
        )
        part_applied = sum(
            n.consensus_reactor.part_announces_applied for n in net.nodes
        )
    finally:
        net.stop()
    # 4 validators x 2 vote types x >=5 heights x 4 nodes: accepts move
    assert acc >= 4 * 2 * 5 * 4
    # redundant pushes exist at all (the problem being engineered down)
    # and land on the duplicates counter, not the accepts
    assert dups > 0
    ratio = dups / acc
    assert 0 < ratio < 10, ratio
    # the dedup mechanisms engaged: announcements fed the mirrors and
    # part screens crossed the wire in both directions
    assert applied > 0
    assert part_sent > 0
    assert part_applied > 0


def test_wake_ups_do_not_raise_the_duplicate_ratio_on_live_net(tmp_path):
    """The gossip routines no longer sleep through a new vote (round
    26), and the lazy-relay hold is what keeps an immediate wake-up from
    becoming an immediate duplicate push: our own vote goes to every
    peer at once, a relayed one waits out its hold, and by then the
    peer has announced it. The bound is read off the parent, whose
    routines polled: the same net at the same pacing read 0.257-0.333
    over three runs (this change: 0.000-0.207). The wake counters show
    that the sends were signalled, not found by the back-stop."""
    from tests.netchaos_common import ChaosNet

    net = ChaosNet(
        4, str(tmp_path / "wake"), gossip_dedup=True, height_throttle_s=0.25,
    )
    net.start()
    try:
        assert net.wait_height(10, timeout=150), net.heights()
        dups = sum(n.consensus_state.vote_duplicates for n in net.nodes)
        acc = sum(n.consensus_state.vote_accepted for n in net.nodes)
        reactors = [n.consensus_reactor for n in net.nodes]
        sends = sum(r.gossip_sends for r in reactors)
        backstop_sends = sum(r.gossip_backstop_sends for r in reactors)
        woken = sum(r.gossip_wakes_event for r in reactors)
        announces = sum(r.gossip_announces_sent for r in reactors)
        announced = sum(r.gossip_announce_bits for r in reactors)
        applied = sum(r.has_votes_applied for r in reactors)
    finally:
        net.stop()
    assert acc >= 4 * 2 * 9 * 3
    assert dups / acc <= 0.34, f"duplicate ratio {dups}/{acc} above the parent's"
    assert sends > 0 and woken > 0
    assert backstop_sends <= 0.05 * sends, (backstop_sends, sends)
    # the announcements coalesce (PR 31): one HasVote a peer a vote was
    # more than vote_accepted x 3 peers messages (own votes on top);
    # every vote is still announced, and the bits reach the mirrors
    assert 0 < announces < acc * 3, (announces, acc)
    assert announced >= acc
    assert announced > announces / 3, "a flush held one vote on average"
    assert applied > 0


@pytest.mark.slow
def test_dedup_reduces_duplicate_ratio_on_live_net(tmp_path):
    """The directional claim on a live 4-node real-TCP net: dedup on
    (HasVote exploitation + lazy-relay hold) yields a strictly lower
    fleet duplicate-vote ratio than off, at real commit pacing (the
    hold needs a cadence where announcements can land; the unthrottled
    test preset commits heights faster than a gossip tick)."""
    from tests.netchaos_common import ChaosNet

    def ratio(dedup: bool, sub: str) -> float:
        net = ChaosNet(
            4, str(tmp_path / sub), gossip_dedup=dedup,
            height_throttle_s=0.25,
        )
        net.start()
        try:
            assert net.wait_height(10, timeout=150), net.heights()
            dups = sum(n.consensus_state.vote_duplicates for n in net.nodes)
            acc = sum(n.consensus_state.vote_accepted for n in net.nodes)
        finally:
            net.stop()
        assert acc > 0
        return dups / acc

    on = ratio(True, "dedup-on")
    off = ratio(False, "dedup-off")
    assert on < off, f"dedup did not reduce duplicates: on={on:.3f} off={off:.3f}"
