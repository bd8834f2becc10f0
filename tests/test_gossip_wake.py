"""The reactor gossips from ONE routine over all its peers (round 33),
woken by the event it used to poll for (round 26).

An event marks the peers it concerns and sets the routine's one signal;
the routine takes the marks, reads the round state once and sweeps the
marked peers, with PEER_GOSSIP_SLEEP as the time-out of its wait. Nothing
here is timed against a 100 ms tick: the back-stop is patched to several
seconds, so a send that shows up within a second can only have been woken
by a signal (or by a relay hold's end, where the test says so).
"""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import pytest

from tendermint_tpu.consensus import messages as msgs
from tendermint_tpu.consensus import reactor as reactor_mod
from tendermint_tpu.consensus.reactor import (
    DATA_CHANNEL,
    PEER_STATE_KEY,
    STATE_CHANNEL,
    VOTE_CHANNEL,
    VOTE_SET_BITS_CHANNEL,
    ConsensusReactor,
    _dec,
    _enc,
)
from tendermint_tpu.consensus.round_state import RoundStep
from tendermint_tpu.consensus.trace import TraceRecorder
from tendermint_tpu.libs.bitarray import BitArray
from tendermint_tpu.libs.events import EventSwitch
from tendermint_tpu.types import PartSet, Proposal, Vote
from tendermint_tpu.types import events as tev
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE

BACKSTOP = 5.0  # what PEER_GOSSIP_SLEEP is patched to
SOON = 1.0      # far below BACKSTOP, far above a scheduler hiccup
HEIGHT = 5


class _VoteSet:
    def __init__(self, type_, size=4):
        self.height, self.round_, self.type_ = HEIGHT, 0, type_
        self._size = size
        self.votes: dict[int, Vote] = {}

    def add(self, index: int) -> Vote:
        vote = Vote(
            validator_address=bytes([index + 1]) * 20, validator_index=index,
            height=self.height, round_=self.round_, type_=self.type_,
            block_id=BlockID(),
        )
        self.votes[index] = vote
        return vote

    def size(self):
        return self._size

    def bit_array(self):
        return BitArray.from_indices(self._size, list(self.votes))

    def get_by_index(self, index):
        return self.votes[index]

    def bit_array_by_block_id(self, block_id):
        return self.bit_array()


class _Votes:
    def __init__(self):
        self.pre = _VoteSet(VOTE_TYPE_PREVOTE)
        self.pc = _VoteSet(VOTE_TYPE_PRECOMMIT)

    def prevotes(self, round_):
        return self.pre if round_ == 0 else None

    def precommits(self, round_):
        return self.pc if round_ == 0 else None


class _ConState:
    """As much of ConsensusState as the reactor reads."""

    def __init__(self):
        self.config = SimpleNamespace(gossip_dedup=True)
        self.rs = SimpleNamespace(
            height=HEIGHT, round_=0, step=RoundStep.PREVOTE,
            start_time=time.time(),
            validators=SimpleNamespace(size=lambda: 4),
            last_validators=None, last_commit=None, votes=_Votes(),
            proposal=None, proposal_block_parts=None, proposal_block=None,
        )
        self.vote_recv_mono: dict = {}
        self.own_entered_mono: dict = {}
        self.trace = TraceRecorder()
        self.trace.begin(HEIGHT)
        self.gossip_wake = None
        self.round_state_reads = 0

    def get_round_state(self):
        self.round_state_reads += 1
        return self.rs

    def stop(self):
        pass


class _Peer:
    def __init__(self, name: str, log: list | None = None):
        self._id = name
        self._kv: dict = {}
        self.sent: list = []  # (monotonic, channel, decoded message)
        self.full: set = set()  # channels whose send queue is "full"
        self.refused = 0
        self.on_send = None   # called with the message, before it is kept
        self._log = log       # the net's: ids in the order of the sends
        self._cond = threading.Condition()

    def id(self):
        return self._id

    def get(self, k):
        return self._kv.get(k)

    def set(self, k, v):
        self._kv[k] = v

    def send(self, ch, raw):
        msg = _dec(raw)
        if self.on_send is not None:
            self.on_send(msg)
        with self._cond:
            self.sent.append((time.monotonic(), ch, msg))
            if self._log is not None:
                self._log.append((self._id, type(msg)))
            self._cond.notify_all()
        return True

    def try_send(self, ch, raw):
        if ch in self.full:
            self.refused += 1
            return False
        return self.send(ch, raw)

    def of(self, cls) -> list:
        with self._cond:
            return [(t, m) for t, _ch, m in self.sent if isinstance(m, cls)]

    def wait_for(self, cls, n: int = 1, timeout: float = SOON) -> bool:
        deadline = time.monotonic() + timeout
        with self._cond:
            while sum(isinstance(m, cls) for _t, _c, m in self.sent) < n:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(left)
        return True


class _Switch:
    """Switch.broadcast over the net's stub peers."""

    def __init__(self, peers: list):
        self.peers = peers

    def broadcast(self, ch, raw):
        for p in self.peers:
            p.try_send(ch, raw)


class _Net:
    """One reactor over a stub consensus state, with stub peers whose
    mirrors sit at our height and round. `switch` gives the reactor one
    to broadcast on (without it, as in a harness reactor, a broadcast
    is a no-op). With `start=False` no routine runs and the test makes
    the sweeps itself (`net.r._gossip_sweep()`), one at a time."""

    def __init__(self, n_peers: int = 1, at_our_height: bool = True,
                 switch: bool = False, start: bool = True):
        self.cs = _ConState()
        self.r = ConsensusReactor(self.cs)
        self.r._started = True  # the routine guards on is_running()
        self.evsw = EventSwitch()
        self.evsw.start()
        self.r.set_event_switch(self.evsw)
        self.log: list = []
        self.peers = [_Peer(f"peer-{i:04d}", self.log) for i in range(n_peers)]
        if switch:
            self.r.switch = _Switch(self.peers)
        if start:
            self.r._start_gossip()
        for p in self.peers:
            self.r.add_peer(p)
            if at_our_height:
                self.step_to(p, HEIGHT)

    def ps(self, peer):
        return peer.get(PEER_STATE_KEY)

    def step_to(self, peer, height: int, step=RoundStep.PREVOTE) -> None:
        self.r.receive(STATE_CHANNEL, peer, _enc(msgs.NewRoundStepMessage(
            height=height, round_=0, step=step,
            seconds_since_start_time=0, last_commit_round=-1,
        )))

    def settle(self) -> None:
        """Let the routine finish the sweeps its start and the set-up's
        marks caused, so that it sits in its wait."""
        deadline = time.monotonic() + SOON
        while time.monotonic() < deadline:
            before = self.waits()
            time.sleep(0.05)
            if self.waits() == before:
                return
        raise AssertionError("the routine never came to rest")

    def waits(self) -> int:
        r = self.r
        return (r.gossip_wakes_event + r.gossip_wakes_hold
                + r.gossip_wakes_backstop + r.gossip_peer_looks)

    def own_vote(self, index: int, type_=VOTE_TYPE_PREVOTE) -> Vote:
        """Our own vote enters the vote set and its event fires."""
        votes = self.cs.rs.votes
        vote = (votes.pre if type_ == VOTE_TYPE_PREVOTE else votes.pc).add(index)
        self.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(vote))
        return vote

    def close(self) -> None:
        for p in self.peers:
            self.r.remove_peer(p, "test over")
        self.r.on_stop()
        self.evsw.stop()
        if self.r._gossip_thread is not None:
            self.r._gossip_thread.join(SOON)


@pytest.fixture
def net_factory(monkeypatch):
    monkeypatch.setattr(reactor_mod, "PEER_GOSSIP_SLEEP", BACKSTOP)
    nets: list[_Net] = []

    def make(*a, **kw) -> _Net:
        net = _Net(*a, **kw)
        nets.append(net)
        return net

    yield make
    for net in nets:
        net.close()


def test_own_vote_goes_out_on_its_event(net_factory):
    """Our own prevote enters the vote set and EVENT_VOTE fires: the
    routine sends it to the peer at once, with its lag on the trace."""
    net = net_factory()
    peer = net.peers[0]
    net.settle()
    assert not peer.of(msgs.VoteMessage)

    vote = net.cs.rs.votes.pre.add(2)
    key = (HEIGHT, 0, VOTE_TYPE_PREVOTE, 2)
    net.cs.own_entered_mono[key] = t0 = time.monotonic()
    net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(vote))

    assert peer.wait_for(msgs.VoteMessage), "no wake: the vote sat out a back-stop"
    t_sent, sent = peer.of(msgs.VoteMessage)[0]
    assert sent.vote.validator_index == 2
    assert t_sent - t0 < SOON
    assert net.r.gossip_backstop_sends == 0
    # first send of an item of our own origin: stamp popped, lag noted
    assert key not in net.cs.own_entered_mono
    lag = net.cs.trace.finish(HEIGHT, 1.0).aux["gossip_send_lag_s"]
    assert 0.0 <= lag < SOON


def test_proposal_then_part_go_out_on_their_signals(net_factory):
    """set_proposal fires no event: the state calls the reactor's wake
    directly (the reactor hangs it on the state). The part follows on
    EVENT_PROPOSAL_BLOCK_PART. The routine sends proposal, then part,
    each once."""
    net = net_factory()
    peer = net.peers[0]
    net.settle()
    assert net.cs.gossip_wake == net.r.wake_gossip

    parts = PartSet.from_data(b"block" * 8, 64)
    assert parts.total == 1
    rs = net.cs.rs
    rs.proposal = Proposal(
        height=HEIGHT, round_=0, block_parts_header=parts.header(),
        pol_round=-1, pol_block_id=BlockID(),
    )
    net.cs.gossip_wake()
    assert peer.wait_for(msgs.ProposalMessage)
    assert not peer.of(msgs.BlockPartMessage)  # we hold no part yet

    rs.proposal_block_parts = parts
    net.evsw.fire_event(
        tev.EVENT_PROPOSAL_BLOCK_PART, tev.EventDataBlockPart(HEIGHT, 0, 0)
    )
    assert peer.wait_for(msgs.BlockPartMessage)
    net.settle()
    assert len(peer.of(msgs.ProposalMessage)) == 1
    assert len(peer.of(msgs.BlockPartMessage)) == 1
    assert peer.of(msgs.ProposalMessage)[0][0] < peer.of(msgs.BlockPartMessage)[0][0]
    assert net.r.gossip_backstop_sends == 0


def test_peer_step_into_our_height_wakes_that_peer_only(net_factory):
    """The case that cost the proposer a second sleep: the proposal is
    there, the peer's NewRoundStep for our height is not. When it
    comes, that peer is looked at again; the other peer is not."""
    net = net_factory(n_peers=2, at_our_height=False)
    late, other = net.peers
    rs = net.cs.rs
    parts = PartSet.from_data(b"block" * 8, 64)
    rs.proposal = Proposal(
        height=HEIGHT, round_=0, block_parts_header=parts.header(),
        pol_round=-1, pol_block_id=BlockID(),
    )
    rs.votes.pre.add(1)
    net.r.wake_gossip()
    net.settle()
    assert not late.of(msgs.ProposalMessage) and not late.of(msgs.VoteMessage)

    wakes, looks = net.r.gossip_wakes_event, net.r.gossip_peer_looks
    net.step_to(late, HEIGHT)
    assert late.wait_for(msgs.ProposalMessage)
    assert late.wait_for(msgs.VoteMessage)
    net.settle()
    # one wait ended on the signal, and the sweep looked at late alone
    assert net.r.gossip_wakes_event - wakes == 1
    assert net.r.gossip_peer_looks - looks == 1
    assert not other.of(msgs.ProposalMessage) and not other.of(msgs.VoteMessage)


def _receive_case(what: str):
    bits = BitArray(4)
    vote = _VoteSet(VOTE_TYPE_PREVOTE).add(3)
    parts = PartSet.from_data(b"block" * 8, 64)
    return {
        "has_vote": (STATE_CHANNEL, msgs.HasVoteMessage(HEIGHT, 0, VOTE_TYPE_PREVOTE, 3)),
        "has_votes": (STATE_CHANNEL, msgs.HasVotesMessage(
            HEIGHT, 0, VOTE_TYPE_PREVOTE, BitArray.from_indices(4, [1, 3]))),
        "has_block_part": (STATE_CHANNEL, msgs.HasBlockPartMessage(HEIGHT, 0, 0)),
        "vote": (VOTE_CHANNEL, msgs.VoteMessage(vote)),
        "block_part": (DATA_CHANNEL, msgs.BlockPartMessage(HEIGHT, 0, parts.get_part(0))),
        "commit_step": (STATE_CHANNEL, msgs.CommitStepMessage(
            HEIGHT, parts.header(), BitArray(1))),
        "proposal_pol": (DATA_CHANNEL, msgs.ProposalPOLMessage(HEIGHT, 0, bits)),
        "vote_set_bits": (VOTE_SET_BITS_CHANNEL, msgs.VoteSetBitsMessage(
            HEIGHT, 0, VOTE_TYPE_PREVOTE, BlockID(), bits)),
        "vote_set_maj23": (STATE_CHANNEL, msgs.VoteSetMaj23Message(
            HEIGHT, 0, VOTE_TYPE_PREVOTE, BlockID())),
    }[what]


@pytest.mark.parametrize(
    "what,looked_at",
    [
        # only ever take away from what is sendable to the peer
        ("has_vote", 0), ("has_votes", 0), ("has_block_part", 0), ("vote", 0),
        ("block_part", 0),
        # can add to it: that peer, nobody else
        ("commit_step", 1), ("proposal_pol", 1), ("vote_set_bits", 1),
        ("vote_set_maj23", 1),
    ],
)
def test_a_peers_message_wakes_its_routines_only_if_it_can_add(
        net_factory, what, looked_at):
    """HasVote, its burst form, HasBlockPart and a received vote's or
    part's own mirror bit only REDUCE what is sendable: no mark, no wake.
    What the peer asks for or steps into marks that peer, and the sweep
    looks at it alone (three peers here: three looks if it took all)."""
    net = net_factory(n_peers=3)
    net.cs.try_add_peer_message = lambda msg, peer_id: True
    net.cs.rs.votes.set_peer_maj23 = lambda *a: None
    net.settle()
    wakes, looks = net.r.gossip_wakes_event, net.r.gossip_peer_looks
    ch, msg = _receive_case(what)
    net.r.receive(ch, net.peers[0], _enc(msg))
    net.settle()
    assert net.r.gossip_wakes_event - wakes == looked_at
    assert net.r.gossip_peer_looks - looks == looked_at
    assert net.r.gossip_sends == 0


def test_held_vote_goes_out_when_its_hold_ends(net_factory):
    """A vote we received moments ago is held by the lazy-relay screen.
    Its event marks no peer while it is held (PR 27: that was a wake to
    find it held and one more at the hold's end, per peer and vote); ONE
    deferred mark when the hold ends does — not a back-stop on top of
    it — and nothing is sent before."""
    net = net_factory()
    peer = net.peers[0]
    net.settle()
    hold = net.r._relay_delay(peer.get(PEER_STATE_KEY))
    assert 0.0 < hold < SOON < BACKSTOP
    wakes, looks = net.r.gossip_wakes_event, net.r.gossip_peer_looks

    vote = net.cs.rs.votes.pre.add(1)
    net.cs.vote_recv_mono[(HEIGHT, 0, VOTE_TYPE_PREVOTE, 1)] = t0 = time.monotonic()
    net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(vote))

    assert peer.wait_for(msgs.VoteMessage, timeout=hold + SOON)
    t_sent = peer.of(msgs.VoteMessage)[0][0]
    assert t_sent - t0 >= hold, "sent inside its hold"
    assert t_sent - t0 < hold + SOON, "waited a back-stop on top of the hold"
    net.settle()
    # one wake at the hold's end, one look, one send
    assert net.r.gossip_wakes_event - wakes == 1
    assert net.r.gossip_peer_looks - looks == 1 and net.r.gossip_sends == 1
    assert net.r.gossip_backstop_sends == 0


def test_a_hold_found_at_the_look_ends_the_wait_by_itself(net_factory):
    """A look that finds only held votes (a mark brought it there before
    their time) makes the routine wait for the earliest hold of any
    peer, not for the back-stop: no deferred mark is pending here."""
    net = net_factory(n_peers=2)
    net.settle()
    hold = 0.3
    vote = net.cs.rs.votes.pre.add(1)
    key = (HEIGHT, 0, VOTE_TYPE_PREVOTE, 1)
    net.cs.vote_recv_mono[key] = time.monotonic() + hold - net.r._relay_delay(
        net.ps(net.peers[0]))
    holds = net.r.gossip_wakes_hold
    net.r.wake_votes_gossip()   # not EVENT_VOTE: nothing is deferred
    for p in net.peers:
        assert p.wait_for(msgs.VoteMessage, timeout=hold + SOON)
        assert p.of(msgs.VoteMessage)[0][1].vote == vote
    assert net.r.gossip_wakes_hold > holds
    assert net.r.gossip_backstop_sends == 0


def test_own_vote_is_not_kept_back_by_a_held_one(net_factory):
    """Two votes the peer lacks, one of them held: the pick passes over
    the held one, whichever the random pick lands on first."""
    net = net_factory()
    peer = net.peers[0]
    net.settle()
    pre = net.cs.rs.votes.pre
    for i in (0, 1, 3):
        pre.add(i)
        net.cs.vote_recv_mono[(HEIGHT, 0, VOTE_TYPE_PREVOTE, i)] = time.monotonic() + 60
    own = pre.add(2)
    net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(own))
    assert peer.wait_for(msgs.VoteMessage)
    net.settle()
    assert [m.vote.validator_index for _t, m in peer.of(msgs.VoteMessage)] == [2]


def test_event_between_the_look_and_the_wait_is_not_lost(net_factory):
    """The signal is cleared and the marks are taken BEFORE the round
    state is read. Drive the worst interleaving with a hook: the vote
    appears and its event fires after the sweep has looked (and found
    nothing) and before the routine waits. The wait must end at once."""
    net = net_factory()
    peer = net.peers[0]
    net.settle()
    real_sweep = net.r._gossip_sweep
    fired = threading.Event()

    def sweep_then_a_late_event(everyone=False):
        out = real_sweep(everyone)
        if not fired.is_set():
            fired.set()
            net.own_vote(0)
        return out

    net.r._gossip_sweep = sweep_then_a_late_event
    # one more (empty) sweep, ending in the hooked moment before the wait
    net.r.wake_gossip()
    assert fired.wait(SOON)
    assert peer.wait_for(msgs.VoteMessage), "the wake-up was lost"


def test_marks_set_from_many_threads_are_never_lost(net_factory):
    """The marks are plain attributes that the threads firing events set
    and the routine takes without a lock. More markers than cores, a
    switch interval of 10 us, and in every round each marker makes one
    vote sendable and then marks all peers, all at the same instant: a
    mark lost to the routine's take would leave a vote to the back-stop
    (5 s here), and the round would not complete."""
    import sys

    net = net_factory(n_peers=3)
    pre = net.cs.rs.votes.pre
    pre._size = 240
    net.cs.rs.validators = SimpleNamespace(size=lambda: 240)
    net.r.wake_gossip()
    net.settle()
    markers, rounds = 16, 15
    gate = threading.Barrier(markers)

    def mark(k: int) -> None:
        for r in range(rounds):
            gate.wait(SOON * 10)
            pre.add(r * markers + k)       # the state first, then the mark
            net.r.wake_votes_gossip()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=mark, args=(k,)) for k in range(markers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(SOON * 20)
        assert not any(t.is_alive() for t in threads)
        for p in net.peers:
            assert p.wait_for(msgs.VoteMessage, n=markers * rounds), \
                len(p.of(msgs.VoteMessage))
    finally:
        sys.setswitchinterval(was)
    for p in net.peers:
        got = sorted(m.vote.validator_index for _t, m in p.of(msgs.VoteMessage))
        assert got == list(range(markers * rounds))
    assert net.r.gossip_backstop_sends == 0 and net.r.gossip_wakes_backstop == 0


def test_idle_routine_backs_its_back_stop_off_and_an_event_resets_it():
    """No spin, at the real back-stop: the routine with nothing to send,
    woken by nothing, looks after 0.1, 0.2, 0.4 and then every 0.8 s
    (PR 27: at ten looks a second each, a committee node's 62 routines
    were 620 thread switches a second that found nothing; it is one
    routine now). An event puts it back to 0.1 s."""
    sleep = reactor_mod.PEER_GOSSIP_SLEEP
    top = reactor_mod.GOSSIP_BACKSTOP_MAX_SLEEPS
    assert top == 8 and sleep == 0.1
    net = _Net(n_peers=3)
    try:
        time.sleep(2.0)  # 0.1 + 0.2 + 0.4 + 0.8: at the longest wait now
        before = (net.r.gossip_wakes_backstop, net.r.gossip_wakes_event,
                  net.r.gossip_peer_looks)
        time.sleep(2.0)
        backstops = net.r.gossip_wakes_backstop - before[0]
        assert net.r.gossip_wakes_event - before[1] == 0
        # ONE routine whatever the number of peers, a look every 0.8 s,
        # and a back-stop looks at all three
        assert 1 <= backstops <= 3, backstops
        assert net.r.gossip_peer_looks - before[2] == 3 * backstops
        net.r.wake_gossip()          # found nothing either: back to 0.1 s
        time.sleep(0.05)
        before = net.r.gossip_wakes_backstop
        time.sleep(0.65)             # 0.1 + 0.2 end inside, 0.4 may
        fast = net.r.gossip_wakes_backstop - before
        assert 2 <= fast <= 3, fast
    finally:
        net.close()
    assert net.r.gossip_sends == 0 and net.r.gossip_backstop_sends == 0


def test_a_wake_that_finds_nothing_goes_back_to_a_full_wait(net_factory):
    net = net_factory()
    net.settle()
    wakes, looks = net.r.gossip_wakes_event, net.r.gossip_peer_looks
    for _ in range(3):
        net.r.wake_gossip()
        net.settle()
    # three wakes, one routine: three waits ended, and no more than that
    assert net.r.gossip_wakes_event - wakes == 3
    assert net.r.gossip_peer_looks - looks == 3
    assert net.r.gossip_sends == 0 and net.r.gossip_wakes_backstop == 0


def test_remove_peer_ends_both_routines_promptly(net_factory):
    """A peer that goes has no routine to end any more: it is out of the
    sweep at once, a mark that still lands on it wakes nobody, and the
    one routine serves the peer that stays."""
    net = net_factory(n_peers=2)
    gone, stays = net.peers
    net.settle()
    gossip = net.r._gossip_thread
    assert gossip.is_alive() and gossip.name == "conR.gossip"
    marks = net.ps(gone).gossip
    net.r.remove_peer(gone, "gone")
    assert net.r._states == (net.ps(stays),)
    assert marks.stopped
    wakes = net.r.gossip_wakes_event
    marks.wake()                      # a deferred mark that was pending
    net.settle()
    assert net.r.gossip_wakes_event == wakes

    net.own_vote(2)
    assert stays.wait_for(msgs.VoteMessage)
    net.settle()
    assert not gone.of(msgs.VoteMessage)
    assert gossip.is_alive()


def test_on_stop_ends_every_peers_routines(net_factory):
    """The stop must end the wait too: with a back-stop of seconds the
    one routine is gone in far less."""
    net = net_factory(n_peers=3)
    net.settle()
    gossip = net.r._gossip_thread
    assert gossip.is_alive()
    t0 = time.monotonic()
    net.r.on_stop()
    gossip.join(SOON)
    assert not gossip.is_alive()
    assert time.monotonic() - t0 < SOON


def test_add_peer_starts_no_thread(net_factory):
    """The reactor's thread count is independent of the number of its
    peers: on_start starts conR.gossip, add_peer starts nothing."""
    net = net_factory(n_peers=1)
    net.settle()
    before = {t.ident for t in threading.enumerate()}
    more = [_Peer(f"more-{i:04d}") for i in range(15)]
    for p in more:
        net.r.add_peer(p)
        net.step_to(p, HEIGHT)
    net.peers.extend(more)
    net.own_vote(1)
    for p in more:
        assert p.wait_for(msgs.VoteMessage)
    started = [t.name for t in threading.enumerate()
               if t.ident not in before and t.name.startswith("conR")]
    assert started == []
    ours = [t.name for t in threading.enumerate() if t.name.startswith("conR.")]
    assert not [n for n in ours if n.startswith(
        ("conR.gossipData", "conR.gossipVotes", "conR.queryMaj23"))]
    assert net.r._gossip_thread.is_alive()


def test_a_full_vote_channel_delays_no_other_peer_and_is_retried(
        net_factory, monkeypatch):
    """try_send, never send: a peer whose VOTE queue is full is passed
    over, the same vote reaches the other peers in the same sweep, the
    full peer's mirror bit stays clear, and it is looked at again after
    SEND_FULL_RETRY (a deferred mark, not the back-stop)."""
    monkeypatch.setattr(reactor_mod, "SEND_FULL_RETRY", 0.05)
    net = net_factory(n_peers=3)
    a, slow, c = net.peers
    slow.send = None   # a blocking send to this peer would raise
    slow.full.add(VOTE_CHANNEL)
    net.settle()
    t0 = time.monotonic()
    net.own_vote(2)
    for p in (a, c):
        assert p.wait_for(msgs.VoteMessage)
        assert p.of(msgs.VoteMessage)[0][0] - t0 < SOON
    # retried, and refused, until the queue has room
    deadline = time.monotonic() + SOON
    while slow.refused < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert slow.refused >= 3
    assert net.r.gossip_send_full == slow.refused
    assert not net.ps(slow).prs.prevotes.get_index(2), "marked without a send"
    assert not slow.of(msgs.VoteMessage)
    slow.send = _Peer.send.__get__(slow)
    slow.full.clear()
    assert slow.wait_for(msgs.VoteMessage)
    net.settle()
    assert net.ps(slow).prs.prevotes.get_index(2)
    assert [len(p.of(msgs.VoteMessage)) for p in net.peers] == [1, 1, 1]
    assert net.r.gossip_backstop_sends == 0 and net.r.gossip_wakes_backstop == 0


def test_the_order_of_peers_rotates_from_sweep_to_sweep(net_factory):
    net = net_factory(n_peers=3, start=False)
    net.r._gossip_sweep()  # the marks of the set-up
    turn = net.r._gossip_turn
    orders = []
    for index in range(3):
        del net.log[:]
        net.own_vote(index)
        sent, _hold = net.r._gossip_sweep()
        assert sent == 3
        orders.append([int(pid[-1]) for pid, cls in net.log
                       if cls is msgs.VoteMessage])
    first = turn % 3
    assert orders == [[(first + k + i) % 3 for i in range(3)] for k in range(3)]


def test_a_peer_removed_in_the_middle_of_a_sweep_gets_nothing_more(net_factory):
    net = net_factory(n_peers=3, start=False)
    net.r._gossip_sweep()
    first = net.peers[net.r._gossip_turn % 3]
    rest = [p for p in net.peers if p is not first]
    # the sweep's first send takes the other two out: one as remove_peer
    # does it, one whose connection breaks under the sweep's hands
    def boom(ch, raw):
        raise OSError("connection reset")

    def first_send(msg):
        first.on_send = None
        net.r.remove_peer(rest[0], "gone mid-sweep")
        rest[1].try_send = boom

    first.on_send = first_send
    for i in (0, 1):
        net.cs.rs.votes.pre.add(i)
    net.r.wake_votes_gossip()
    sent, _hold = net.r._gossip_sweep()
    assert sent == 2 and len(first.of(msgs.VoteMessage)) == 2
    assert not rest[0].of(msgs.VoteMessage) and not rest[1].of(msgs.VoteMessage)
    assert net.r.gossip_peer_looks >= 3


class _Store:
    """A block store that holds one committed height."""

    def __init__(self, height: int, parts: PartSet, precommits: list):
        self.height, self.parts, self.precommits = height, parts, precommits
        self.reads: list = []

    def load_block_meta(self, height):
        if height != self.height:
            return None
        return SimpleNamespace(block_id=SimpleNamespace(
            parts_header=self.parts.header()))

    def load_block_part(self, height, index):
        self.reads.append(("part", height, index))
        return self.parts.get_part(index)

    def load_block_commit(self, height):
        self.reads.append(("commit", height))
        return SimpleNamespace(precommits=self.precommits, round_=lambda: 0)


def test_a_peer_behind_gets_one_stored_item_a_sweep(net_factory):
    """Store-backed catch-up is one item a lagging peer a sweep, after
    the peers at our height, which get all of theirs; the lagging peer
    stays marked, so the next sweep follows at once, and it ends up with
    every part and every precommit of the height it is at."""
    net = net_factory(n_peers=3, at_our_height=False, start=False)
    behind, here, there = net.peers
    parts = PartSet.from_data(bytes(range(200)), 64)
    old = _VoteSet(VOTE_TYPE_PRECOMMIT)
    old.height = HEIGHT - 2
    net.cs.block_store = _Store(
        HEIGHT - 2, parts, [old.add(i) for i in range(3)] + [None])
    net.step_to(behind, HEIGHT - 2)
    for p in (here, there):
        net.step_to(p, HEIGHT)
    for i in range(3):
        net.cs.rs.votes.pre.add(i)
    net.r.wake_gossip()

    sent, _hold = net.r._gossip_sweep()
    assert sent == 3 + 3 + 1
    for p in (here, there):
        assert len(p.of(msgs.VoteMessage)) == 3
    assert len(net.cs.block_store.reads) == 1
    # the peers at our height were served before the store was read
    assert [pid for pid, _cls in net.log[-1:]] == [behind.id()]
    assert net.r._gossip_signal.is_set(), "the lagging peer is not marked"

    sweeps = 1
    while net.r._gossip_sweep()[0]:
        sweeps += 1
        assert len(net.cs.block_store.reads) <= sweeps + 1
        assert sweeps < 20
    got_parts = sorted(m.part.index for _t, m in behind.of(msgs.BlockPartMessage))
    assert got_parts == list(range(parts.total)) and parts.total == 4
    got_votes = sorted(m.vote.validator_index for _t, m in behind.of(msgs.VoteMessage))
    assert got_votes == [0, 1, 2]
    assert sweeps == parts.total + 3
    assert len(here.of(msgs.VoteMessage)) == 3


def test_maj23_claims_reach_every_peer_within_the_query_sleep(
        net_factory, monkeypatch):
    """queryMaj23 is a duty of the same routine: every
    PEER_QUERY_MAJ23_SLEEP, all peers at our height in one pass."""
    monkeypatch.setattr(reactor_mod, "PEER_QUERY_MAJ23_SLEEP", 0.2)
    net = net_factory(n_peers=3, at_our_height=False)
    maj = BlockID(hash=b"\x07" * 20)
    net.cs.rs.votes.pre.two_thirds_majority = lambda: maj
    net.cs.rs.votes.pc.two_thirds_majority = lambda: None
    for p in net.peers[:2]:
        net.step_to(p, HEIGHT)
    t0 = time.monotonic()
    for p in net.peers[:2]:
        assert p.wait_for(msgs.VoteSetMaj23Message, timeout=0.2 + SOON)
        t, claim = p.of(msgs.VoteSetMaj23Message)[0]
        assert t - t0 < 0.2 + SOON
        assert (claim.height, claim.round_, claim.type_) == (HEIGHT, 0, VOTE_TYPE_PREVOTE)
        assert claim.block_id == maj
        assert p.wait_for(msgs.VoteSetMaj23Message, n=2, timeout=0.2 + SOON)
    assert not net.peers[2].of(msgs.VoteSetMaj23Message)  # not at our height
    assert net.r.gossip_wakes_backstop == 0  # the claims' own timer


def test_a_vote_is_encoded_once_for_fifteen_peers(net_factory, monkeypatch):
    """One json.dumps a vote a sweep, not one a peer; and the round state
    is read once a sweep, not once a peer an item."""
    net = net_factory(n_peers=15, start=False)
    net.r._gossip_sweep()
    encoded = []
    real_enc = reactor_mod._enc

    def counting_enc(msg):
        encoded.append(type(msg))
        return real_enc(msg)

    monkeypatch.setattr(reactor_mod, "_enc", counting_enc)
    for i in (0, 1):
        net.cs.rs.votes.pre.add(i)
    net.r.wake_votes_gossip()
    reads = net.cs.round_state_reads
    sent, _hold = net.r._gossip_sweep()
    assert sent == 30 and net.r.gossip_sends == 30
    assert encoded.count(msgs.VoteMessage) == 2
    assert net.cs.round_state_reads - reads == 1
    for p in net.peers:
        assert sorted(m.vote.validator_index
                      for _t, m in p.of(msgs.VoteMessage)) == [0, 1]


def _announced(peer) -> list:
    """What `peer` was told we hold: (key, indices) of each HasVotesMessage."""
    return [((m.height, m.round_, m.type_), m.votes.indices())
            for _t, m in peer.of(msgs.HasVotesMessage)]


def test_a_burst_of_votes_is_one_announcement_a_peer(net_factory, monkeypatch):
    """k votes that enter our vote set inside the delay are ONE
    HasVotesMessage of k bits to each peer, VOTE_RELAY_DELAY_MIN after
    the first of them, and no single HasVote; the set is emptied, so a
    second burst is a second message that holds its own bits only. (The
    delay is stretched so that a loaded machine cannot split a burst.)"""
    monkeypatch.setattr(reactor_mod, "VOTE_RELAY_DELAY_MIN", 0.3)
    net = net_factory(n_peers=3, switch=True)
    net.settle()
    pre, pc = net.cs.rs.votes.pre, net.cs.rs.votes.pc
    t0 = time.monotonic()
    for i in (0, 2, 3):
        net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(pre.add(i)))
    key = (HEIGHT, 0, VOTE_TYPE_PREVOTE)
    for p in net.peers:
        assert p.wait_for(msgs.HasVotesMessage)
        assert _announced(p) == [(key, [0, 2, 3])]
        t_sent, sent = p.of(msgs.HasVotesMessage)[0]
        assert sent.votes.size == 4
        assert t_sent - t0 >= reactor_mod.VOTE_RELAY_DELAY_MIN, "sent before the burst was over"
        assert not p.of(msgs.HasVoteMessage)
    assert net.r.gossip_announces_sent == 3 and net.r.gossip_announce_bits == 3

    # the second burst spans two vote sets: one message a key
    net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(pre.add(1)))
    net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(pc.add(2)))
    for p in net.peers:
        assert p.wait_for(msgs.HasVotesMessage, n=3)
        assert _announced(p)[1:] == [
            (key, [1]), ((HEIGHT, 0, VOTE_TYPE_PRECOMMIT), [2])]
        assert not p.of(msgs.HasVoteMessage)
    assert net.r.gossip_announces_sent == 9 and net.r.gossip_announce_bits == 5


def test_a_step_broadcast_flushes_the_pending_bits_first(net_factory):
    """A peer's apply_new_round_step resets the arrays of the round a
    step ends, so the bits of that round go out BEFORE the step: on the
    wire HasVotesMessage, then NewRoundStepMessage, without waiting out
    the delay; the timer then finds nothing left to send."""
    net = net_factory(n_peers=2, switch=True)
    net.settle()
    greeted = [len(p.sent) for p in net.peers]  # add_peer sent our step
    vote = net.cs.rs.votes.pre.add(1)
    net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(vote))
    net.cs.rs.step = RoundStep.PRECOMMIT
    net.evsw.fire_event(tev.EVENT_NEW_ROUND_STEP, None)
    for p, n in zip(net.peers, greeted):
        # both were sent inline by the two fire_event calls above
        order = [type(m) for _t, ch, m in p.sent[n:] if ch == STATE_CHANNEL]
        assert order == [msgs.HasVotesMessage, msgs.NewRoundStepMessage]
    time.sleep(2 * reactor_mod.VOTE_RELAY_DELAY_MIN)
    for p in net.peers:
        assert _announced(p) == [((HEIGHT, 0, VOTE_TYPE_PREVOTE), [1])]
    assert net.r.gossip_announces_sent == 2 and net.r.gossip_announce_bits == 1


def test_a_harness_reactor_without_a_switch_keeps_no_bits(net_factory):
    """No switch, nobody to tell: nothing pends and no timer starts."""
    net = net_factory()
    net.evsw.fire_event(
        tev.EVENT_VOTE, tev.EventDataVote(net.cs.rs.votes.pre.add(0)))
    assert net.r._announce_pending == {}
    assert net.r._wakes._thread is None


def test_on_stop_ends_the_announcement_timer(net_factory):
    net = net_factory(switch=True)
    net.evsw.fire_event(
        tev.EVENT_VOTE, tev.EventDataVote(net.cs.rs.votes.pre.add(0)))
    timer = net.r._wakes._thread
    assert timer is not None and timer.is_alive()
    net.cs.stop = lambda: None
    net.r.on_stop()
    timer.join(SOON)
    assert not timer.is_alive()


def test_a_burst_of_vote_events_is_a_handful_of_wakes(net_factory):
    """A committee's votes arrive in a burst: 200 EVENT_VOTEs must cost
    a routine a few wakes, not 200. The signal coalesces, and firing it
    never waits for a routine."""
    net = net_factory()
    peer = net.peers[0]
    net.settle()
    vote = _VoteSet(VOTE_TYPE_PREVOTE).add(0)  # in nobody's vote set
    before = net.r.gossip_wakes_event
    t0 = time.monotonic()
    for _ in range(200):
        net.evsw.fire_event(tev.EVENT_VOTE, tev.EventDataVote(vote))
    fire_s = time.monotonic() - t0
    net.settle()
    wakes = net.r.gossip_wakes_event - before
    assert 1 <= wakes <= 20, wakes  # the votes routine; nowhere near 200
    assert fire_s < SOON
    assert not peer.of(msgs.VoteMessage)


def test_state_signals_the_proposal_and_stamps_its_own_items():
    """The state's side, on a real one-validator ConsensusState: setting
    the proposal calls gossip_wake (there is no event for it), and our
    proposal, its part and our two votes are stamped as they enter the
    round state, for gossip_send_lag_s."""
    from tests.consensus_common import make_cs_and_stubs, wait_for_height

    cs, _stubs, _ = make_cs_and_stubs(1)
    had_proposal = []
    cs.gossip_wake = lambda: had_proposal.append(cs.rs.proposal is not None)
    cs.start()
    try:
        assert wait_for_height(cs, 3, timeout=15)
    finally:
        cs.stop()
    assert had_proposal and all(had_proposal)
    stamped = set(cs.own_entered_mono)  # no reactor here to pop them
    idx = 0
    for h in (1, 2):
        assert ("proposal", h, 0) in stamped
        assert ("part", h, 0, 0) in stamped
        assert (h, 0, VOTE_TYPE_PREVOTE, idx) in stamped
        assert (h, 0, VOTE_TYPE_PRECOMMIT, idx) in stamped
