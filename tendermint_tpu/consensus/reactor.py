"""Consensus gossip reactor (reference: consensus/reactor.go).

Four p2p channels (reactor.go:21-24):
  0x20 STATE       — NewRoundStep / CommitStep / HasVote(s) / ProposalHeartbeat
  0x21 DATA        — Proposal / ProposalPOL / BlockPart
  0x22 VOTE        — Vote
  0x23 VOTE_SET_BITS — VoteSetMaj23 / VoteSetBits

Each peer gets a mirrored PeerRoundState. The reference runs three
goroutines a peer over it (reactor.go:133-135: gossipData, gossipVotes,
queryMaj23); here ONE routine a reactor, `conR.gossip`, does the three
duties for all peers (round 33): a thread a duty a peer was 45 threads at
15 peers, each woken under one GIL to find nothing to send. An event marks
the peers it concerns and sets the routine's one signal; the routine takes
the marks, reads the round state once, and sweeps the marked peers: block
parts + catch-up, the needed-vote picker, every PEER_QUERY_MAJ23_SLEEP the
maj23 claims. It never blocks on a peer (try_send). Step transitions and
new votes are broadcast event-driven via the event switch
(reactor.go:321-337).
"""

from __future__ import annotations

import json
import random
import threading
import time

from tendermint_tpu.consensus import messages as msgs
from tendermint_tpu.consensus.round_state import RoundStep
from tendermint_tpu.libs.bitarray import BitArray
from tendermint_tpu.libs.service import BaseService
from tendermint_tpu.p2p.conn import ChannelDescriptor, MConnConfig
from tendermint_tpu.p2p.ioloop import hold_reads
from tendermint_tpu.p2p.switch import Reactor
from tendermint_tpu.types import events as tev
from tendermint_tpu.types.agg_commit import AggregateLastCommit, commit_is_aggregate
from tendermint_tpu.types.validator_set import CommitError
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE

STATE_CHANNEL = 0x20
DATA_CHANNEL = 0x21
VOTE_CHANNEL = 0x22
VOTE_SET_BITS_CHANNEL = 0x23

# reactor.go peerGossipSleepDuration. Since round 26 the idle back-stop
# of the gossip routine's wait, not its pace: with nothing to send it
# blocks on its signal and is woken by the event that makes something
# sendable (wake_gossip, PeerState.gossip).
PEER_GOSSIP_SLEEP = 0.1
PEER_QUERY_MAJ23_SLEEP = 2.0
# a back-stop that ran out and found nothing doubles the next one, up to
# this many PEER_GOSSIP_SLEEPs; any wake by an event, a hold or a send
# puts it back to one. A timed wait that ends is a thread switch whatever
# the look then costs (PERF.md, PR 27: `gossip_backstop_sends` 0 of
# 37,209 sends in `net4.steady`). A back-stop looks at ALL peers.
GOSSIP_BACKSTOP_MAX_SLEEPS = 8
# a peer whose channel refused an item (try_send: the queue of 100 is
# full) is looked at again after one flush of its connection
SEND_FULL_RETRY = MConnConfig.flush_throttle
# lazy-relay hold (round 20, gossip_dedup): a vote we RECEIVED moments
# ago is being fanned out by its origin right now, and every recipient
# announces it via HasVote within the same window — re-pushing it
# immediately is how k relayers race each other into the 2NxN
# redundancy. One gossip tick is enough for those announcements to set
# the mirror bit (ms on loopback, ~one link RTT under WAN); after it,
# anything still unmarked is genuinely needed and relays normally.
VOTE_RELAY_DELAY = PEER_GOSSIP_SLEEP
# RTT-adaptive hold (round 21): the window that lets HasVote
# announcements win the relay race is ~one link RTT — on a fast LAN the
# 0.1 s constant over-holds (announcements land in ms), under a slow WAN
# it under-holds (re-pushes fire before the announcement arrives). When
# ping RTT samples exist (the p2p ping_rtt EWMA), the hold tracks 2x the
# smoothed RTT (ping->pong is a full round trip; the announcement needs
# one leg each way too), clamped to [0.5x, 4x] of the constant so a
# garbage sample can neither disable the hold nor stall relays. The
# constant remains the exact no-sample fallback.
# Round 32: the RTT is the PEER'S OWN (one smoothed value over a mesh
# whose links are 1-312 ms was wrong for every link of it), a link has
# its first sample as it starts (p2p/conn.py pings at once), and the
# upper clamp is a fixed second: above twice the longest round trip two
# public-cloud regions have (2 x 312 ms), so that the clamp bounds a
# garbage sample and never a real link.
VOTE_RELAY_DELAY_MIN = 0.5 * VOTE_RELAY_DELAY
VOTE_RELAY_DELAY_MAX = 1.0

PEER_STATE_KEY = "ConsensusReactor.peerState"

# the reactor's flat counters of its gossip routine (round 26; the
# sweep's own since round 33), as the `consensus` producer and the
# flight recorder's dumps list them
GOSSIP_COUNTERS = (
    "gossip_sends", "gossip_wakes_event", "gossip_wakes_hold",
    "gossip_wakes_backstop", "gossip_backstop_sends",
    "gossip_announces_sent", "gossip_announce_bits",
    "gossip_sweeps", "gossip_peer_looks", "gossip_send_full",
)

# what one look at one peer came to (_gossip_data_pass,
# _pick_and_send_vote): nothing to send; an item sent; the peer's channel
# refused it; the item has to be read from the store, which waits for
# the sweep's second half
_IDLE, _SENT, _FULL, _STORE = range(4)


def adaptive_relay_delay(rtt_s: float | None) -> float:
    """The lazy-relay hold for a smoothed peer RTT: None (no samples
    yet) keeps the VOTE_RELAY_DELAY constant; otherwise 2x the RTT
    clamped into [VOTE_RELAY_DELAY_MIN, VOTE_RELAY_DELAY_MAX]."""
    if rtt_s is None:
        return VOTE_RELAY_DELAY
    return min(VOTE_RELAY_DELAY_MAX, max(VOTE_RELAY_DELAY_MIN, 2.0 * rtt_s))


def _enc(msg) -> bytes:
    return json.dumps(msgs.msg_to_json(msg), sort_keys=True).encode()


def _enc_once(once: dict, key, make, *args) -> bytes:
    """The encoding of `make(*args)`, made once for the sweep that keeps
    `once` and sent to every peer that needs the item."""
    raw = once.get(key)
    if raw is None:
        raw = once[key] = _enc(make(*args))
    return raw


def _dec(raw: bytes):
    return msgs.msg_from_json(json.loads(raw.decode()))


class PeerRoundState:
    """What we believe the peer's consensus state is (reactor.go:757-773)."""

    def __init__(self):
        self.height = 0
        self.round_ = -1
        self.step = RoundStep.NEW_HEIGHT
        self.start_time = 0.0
        self.proposal = False
        self.proposal_block_parts_header: PartSetHeader | None = None
        self.proposal_block_parts: BitArray | None = None
        self.proposal_pol_round = -1
        self.proposal_pol: BitArray | None = None
        self.prevotes: BitArray | None = None
        self.precommits: BitArray | None = None
        self.last_commit_round = -1
        self.last_commit: BitArray | None = None
        self.catchup_commit_round = -1
        self.catchup_commit: BitArray | None = None


class _PeerGossip:
    """One peer's marks for the reactor's gossip routine: whether its
    data, its votes or both are *to be looked at*, when the earliest
    lazy-relay hold of a vote it needs ends, and whether it is gone.
    `wake*()` sets the mark and then the routine's ONE signal
    (`signal`, the reactor's; None on a PeerState no reactor holds). A
    mark that is set is left alone (one attribute read, no lock), so a
    burst of events costs the thread that fires them next to nothing:
    whoever marks has changed the state first, and the routine takes a
    mark (clears it only where it read it set) BEFORE it reads the state."""

    __slots__ = ("data", "votes", "hold_until", "stopped", "signal")

    def __init__(self):
        self.data = False
        self.votes = False
        self.hold_until: float | None = None
        self.stopped = False
        self.signal: threading.Event | None = None

    def wake(self) -> None:
        self.wake_data()
        self.wake_votes()

    def wake_data(self) -> None:
        if not self.data:
            self.data = True
            self._set()

    def wake_votes(self) -> None:
        if not self.votes:
            self.votes = True
            self._set()

    def _set(self) -> None:
        signal = self.signal
        if signal is not None and not signal.is_set():
            signal.set()

    def end(self) -> None:
        """The peer is gone: a sweep under way gives it nothing more."""
        self.stopped = True
        self.signal = None


class _DeferredWakes:
    """One timer for the whole reactor: `at(fire, deadline)` asks for ONE
    call of `fire` at that instant (time.monotonic). A call asked for
    while the same `fire` is pending rides it (the earlier instant
    stands), so a burst of 31 votes is one mark of a peer's votes when
    its first hold ends, and one announcement when the burst's first
    bit has waited its time, not 31 of either. The calls are made on the
    timer's thread, outside its lock; the thread starts with the first
    call and ends with `stop()`."""

    def __init__(self):
        self._cond = threading.Condition()
        self._due: dict = {}   # fire -> deadline
        self._stopped = False
        self._thread: threading.Thread | None = None

    def at(self, fire, deadline: float) -> None:
        self.at_many(((fire, deadline),))

    def at_many(self, calls) -> None:
        with self._cond:
            if self._stopped:
                return
            fresh = False
            for fire, deadline in calls:
                if fire not in self._due:
                    self._due[fire] = deadline
                    fresh = True
            if not fresh:
                return
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="conR.deferredWakes")
                self._thread.start()
            self._cond.notify()

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify()

    def _run(self) -> None:
        with self._cond:
            while not self._stopped:
                if not self._due:
                    self._cond.wait()
                    continue
                now = time.monotonic()
                left = min(self._due.values()) - now
                if left > 0:
                    self._cond.wait(left)
                    continue
                fire = [f for f, t in self._due.items() if t <= now]
                for f in fire:
                    del self._due[f]
                self._cond.release()
                try:
                    for f in fire:
                        f()
                finally:
                    self._cond.acquire()


def _smoothed(old: float | None, sample: float) -> float:
    """The has-vote lags' average: the first sample as read, then a tenth
    of each new one."""
    return sample if old is None else 0.9 * old + 0.1 * sample


def _peer_label(peer) -> str:
    """Best-effort peer id for metric labels ("?" for harness stubs)."""
    try:
        return peer.id()
    except Exception:  # noqa: BLE001 — labels must never break gossip
        return "?"


class PeerState:
    """Thread-safe mirror + vote bookkeeping for one peer
    (reactor.go:778-1060)."""

    def __init__(self, peer):
        self.peer = peer
        self.prs = PeerRoundState()
        self._mtx = threading.RLock()
        # this peer's marks for the gossip routine; receive() marks it
        # when the mirror changes in a way that can make MORE sendable
        # to this peer
        self.gossip = _PeerGossip()
        # per-peer gossip instrumentation (round 15): child series
        # resolved once — picks vs successful sends is the signal that
        # would have caught the PR-13 pick-marks-before-send wedge
        from tendermint_tpu.p2p.telemetry import peer_metrics

        fams = peer_metrics(getattr(peer, "metrics_registry", None))
        pid = _peer_label(peer)
        self.m_vote_picks = fams["vote_gossip_picks"].labels(peer=pid)
        self.m_vote_sends = fams["vote_gossip_sends"].labels(peer=pid)
        self.m_vote_send_failures = fams["vote_gossip_send_failures"].labels(
            peer=pid
        )
        self.m_catchup_commits = fams["catchup_commits"].labels(peer=pid)
        # aggregate catchup (round 22): one whole-commit send per lagging
        # height, re-armed after a hold so a lost frame can't wedge the
        # peer — (height, monotonic send time) of the last send
        self._agg_commit_sent: tuple[int, float] | None = None
        # smoothed seconds from our receipt of a vote to THIS peer's
        # announcement of it (None until one was seen): what a relay to
        # this peer has to outlast (ConsensusReactor._relay_delay)
        self.has_vote_lag: float | None = None

    def note_has_vote_lag(self, got: float | None) -> float | None:
        """`got`: the vote_recv_mono stamp of a vote this peer has just
        said it holds. Returns the lag it shows, for the reactor's own
        average over all peers."""
        if got is None:
            return None
        lag = time.monotonic() - got
        self.has_vote_lag = _smoothed(self.has_vote_lag, lag)
        return lag

    def rtt_s(self) -> float | None:
        """The link's smoothed ping round trip (None before a sample, and
        for harness peers that measure none)."""
        rtt = getattr(self.peer, "rtt_s", None)
        return rtt() if rtt is not None else None

    # -- reads -------------------------------------------------------------

    def get_round_state(self) -> PeerRoundState:
        with self._mtx:
            import copy

            return copy.copy(self.prs)

    def get_height(self) -> int:
        with self._mtx:
            return self.prs.height

    # -- proposal/parts ----------------------------------------------------

    def set_has_proposal(self, proposal) -> None:
        with self._mtx:
            prs = self.prs
            if prs.height != proposal.height or prs.round_ != proposal.round_:
                return
            if prs.proposal:
                return
            prs.proposal = True
            prs.proposal_block_parts_header = proposal.block_parts_header
            prs.proposal_block_parts = BitArray(proposal.block_parts_header.total)
            prs.proposal_pol_round = proposal.pol_round
            prs.proposal_pol = None  # until ProposalPOLMessage arrives

    def set_has_proposal_block_part(self, height: int, round_: int, index: int) -> None:
        with self._mtx:
            prs = self.prs
            if prs.height != height or prs.round_ != round_:
                return
            if prs.proposal_block_parts is None:
                return
            prs.proposal_block_parts.set_index(index, True)

    def apply_proposal_pol(self, msg: msgs.ProposalPOLMessage) -> None:
        with self._mtx:
            prs = self.prs
            if prs.height != msg.height or prs.proposal_pol_round != msg.proposal_pol_round:
                return
            prs.proposal_pol = msg.proposal_pol

    # -- votes -------------------------------------------------------------

    def set_has_vote(self, height: int, round_: int, type_: int, index: int) -> bool:
        """Mark the peer as holding a vote. Returns True when a tracking
        array existed and the bit landed — False means the coordinates
        matched no array (wrong height/round for this mirror) and the
        information was dropped."""
        with self._mtx:
            ba = self._get_vote_bit_array(height, round_, type_)
            if ba is not None:
                ba.set_index(index, True)
                return True
            return False

    def _get_vote_bit_array(self, height: int, round_: int, type_: int) -> BitArray | None:
        """reactor.go:813-850 — except the round-equal branch must not
        SHADOW the catchup branch with a None: for a peer lagging far
        behind, nothing ever ensures bit arrays at the PEER's height
        (gossip ensures them at OUR heights), so prs.precommits is None
        there and the stored-commit catchup picker would never find a
        tracking array — the round-4 chaos-soak stall."""
        prs = self.prs
        if prs.height == height:
            if prs.round_ == round_:
                ba = prs.prevotes if type_ == VOTE_TYPE_PREVOTE else prs.precommits
                if ba is not None:
                    return ba
            if prs.catchup_commit_round == round_ and type_ == VOTE_TYPE_PRECOMMIT:
                return prs.catchup_commit
            if prs.proposal_pol_round == round_ and type_ == VOTE_TYPE_PREVOTE:
                return prs.proposal_pol
            return None
        if prs.height == height + 1 and prs.last_commit_round == round_ and \
           type_ == VOTE_TYPE_PRECOMMIT:
            return prs.last_commit
        return None

    def ensure_vote_bit_arrays(self, height: int, num_validators: int) -> None:
        with self._mtx:
            prs = self.prs
            if prs.height == height:
                if prs.prevotes is None:
                    prs.prevotes = BitArray(num_validators)
                if prs.precommits is None:
                    prs.precommits = BitArray(num_validators)
                if prs.proposal_pol is None and prs.proposal_pol_round >= 0:
                    prs.proposal_pol = BitArray(num_validators)
                if prs.catchup_commit is None and prs.catchup_commit_round >= 0:
                    prs.catchup_commit = BitArray(num_validators)
            elif prs.height == height + 1:
                if prs.last_commit is None:
                    prs.last_commit = BitArray(num_validators)

    def ensure_catchup_commit_round(self, height: int, round_: int, num_validators: int) -> None:
        """reactor.go:855-873."""
        with self._mtx:
            prs = self.prs
            if prs.height != height or round_ < 0:
                return
            if prs.catchup_commit_round == round_:
                return
            prs.catchup_commit_round = round_
            self.m_catchup_commits.inc()
            # alias the live precommit array only when it EXISTS; a
            # far-behind peer's mirror has none at its own height, and
            # aliasing None here left the catchup picker with no
            # tracking array at all (it must be a fresh BitArray then)
            prs.catchup_commit = (
                prs.precommits
                if prs.round_ == round_ and prs.precommits is not None
                else BitArray(num_validators)
            )

    def pick_vote_to_send(self, vote_set, hold_of=None) -> object | None:
        """A random vote the peer needs from `vote_set` (reactor.go:899-933).
        With `hold_of` (the reactor's lazy-relay screen: seconds a vote
        is still held, 0.0 for none) a held pick is passed over for any
        other needed vote, in random order — so our own fresh vote is
        never kept back by a relay that has to wait.

        Does NOT mark the peer as having it — the caller marks via
        set_has_vote only AFTER peer.send succeeds (reactor.go's
        PickSendVote order). Marking at pick time meant a vote whose
        send failed on a full channel queue (exactly the burst-load
        moment) was skipped for that peer FOREVER — no other mechanism
        resends it, and a 2-2 height split could wedge the whole net
        (the netchaos smoke's stall signature)."""
        if vote_set is None or vote_set.size() == 0:
            return None
        with self._mtx:
            ps_bits = self._get_vote_bit_array(
                vote_set.height, vote_set.round_, vote_set.type_
            )
            if ps_bits is None:
                return None
            needed = vote_set.bit_array().sub(ps_bits)
            if needed.is_empty():
                return None
            index, ok = needed.pick_random()
            if not ok:
                return None
            vote = vote_set.get_by_index(index)
            if hold_of is None or hold_of(vote) <= 0.0:
                return vote
            rest = [i for i in needed.indices() if i != index]
            random.shuffle(rest)
            for i in rest:
                vote = vote_set.get_by_index(i)
                if hold_of(vote) <= 0.0:
                    return vote
            return None

    def agg_commit_due(self, height: int, hold: float = 1.0) -> bool:
        """Whether the aggregate catchup commit for `height` should be
        (re)sent to this peer: never sent, sent for another height, or
        sent over `hold` seconds ago with the peer still stuck there."""
        with self._mtx:
            sent = self._agg_commit_sent
            if sent is None or sent[0] != height:
                return True
            return time.monotonic() - sent[1] >= hold

    def mark_agg_commit_sent(self, height: int) -> None:
        with self._mtx:
            self._agg_commit_sent = (height, time.monotonic())

    # -- step transitions --------------------------------------------------

    def apply_new_round_step(self, msg: msgs.NewRoundStepMessage) -> None:
        """reactor.go:1046-1090."""
        with self._mtx:
            prs = self.prs
            psheight, psround, psstep = prs.height, prs.round_, prs.step
            # stale/duplicate guard (reactor.go:1050-1053): a reordered or
            # replayed step message must never move peer state backwards —
            # without this, an attacker replaying an old NewRoundStep wipes
            # the vote bit-arrays we track for the peer
            if (msg.height, msg.round_, msg.step) <= (psheight, psround, int(psstep)):
                return
            ps_catchup_round = prs.catchup_commit_round
            ps_catchup = prs.catchup_commit

            ps_precommits = prs.precommits  # before the reset below

            prs.height = msg.height
            prs.round_ = msg.round_
            prs.step = msg.step
            prs.start_time = time.time() - msg.seconds_since_start_time
            if psheight != msg.height or psround != msg.round_:
                prs.proposal = False
                prs.proposal_block_parts_header = None
                prs.proposal_block_parts = None
                prs.proposal_pol_round = -1
                prs.proposal_pol = None
                prs.prevotes = None
                prs.precommits = None
            if psheight == msg.height and psround != msg.round_ and \
               msg.round_ == ps_catchup_round:
                prs.precommits = ps_catchup
            if psheight != msg.height:
                # shift the H-precommits the peer had to last_commit
                if psheight + 1 == msg.height and psround == msg.last_commit_round:
                    prs.last_commit_round = msg.last_commit_round
                    prs.last_commit = ps_precommits
                else:
                    prs.last_commit_round = msg.last_commit_round
                    prs.last_commit = None
                prs.catchup_commit_round = -1
                prs.catchup_commit = None

    def apply_commit_step(self, msg: msgs.CommitStepMessage) -> None:
        with self._mtx:
            prs = self.prs
            if prs.height != msg.height:
                return
            prs.proposal_block_parts_header = msg.block_parts_header
            prs.proposal_block_parts = msg.block_parts

    def apply_has_vote(self, msg: msgs.HasVoteMessage,
                       allow_last_commit: bool = False) -> bool:
        """Feed a HasVote announcement into the mirror. The strict gate
        (peer height only) is the pre-round-20 behavior; with
        allow_last_commit (the gossip_dedup knob) a HasVote for the
        height BELOW the peer's also lands — _get_vote_bit_array routes
        it to the last_commit array, which is exactly the height a node
        keeps broadcasting HasVotes for right after committing (those
        announcements were silently dropped before, so the laggard's
        commit votes kept being re-pushed by everyone)."""
        with self._mtx:
            if self.prs.height != msg.height and not (
                allow_last_commit and self.prs.height == msg.height + 1
            ):
                return False
        return self.set_has_vote(msg.height, msg.round_, msg.type_, msg.index)

    def apply_has_votes(self, msg: msgs.HasVotesMessage,
                        allow_last_commit: bool = False) -> tuple[int, list[int]]:
        """Feed a burst's announcement into the mirror: apply_has_vote's
        gate and routing, once and under one lock for the whole array,
        which is ORed in (a bit the mirror holds is never cleared). An
        array of another size than the mirror's is not ours to
        interpret and is ignored. Returns how many bits landed and the
        indices among them the mirror did not hold before."""
        with self._mtx:
            if self.prs.height != msg.height and not (
                allow_last_commit and self.prs.height == msg.height + 1
            ):
                return 0, []
            ba = self._get_vote_bit_array(msg.height, msg.round_, msg.type_)
            if ba is None or ba.size != msg.votes.size:
                return 0, []
            fresh = msg.votes.sub(ba)
            ba.update(ba.or_(msg.votes))
            return msg.votes.num_true_bits(), fresh.indices()

    def apply_vote_set_bits(self, msg: msgs.VoteSetBitsMessage, our_votes: BitArray | None) -> None:
        """reactor.go:1126-1149. ourVotes is a MASK of what we know we
        hold for that BlockID: keep the peer-bits that aren't ours, OR in
        the peer's report, and REPLACE — never mark the peer as having
        votes only we hold."""
        with self._mtx:
            ba = self._get_vote_bit_array(msg.height, msg.round_, msg.type_)
            if ba is None:
                return
            if our_votes is not None:
                ba.update(ba.sub(our_votes).or_(msg.votes))
            else:
                ba.update(msg.votes)


class ConsensusReactor(Reactor, BaseService):
    def __init__(self, consensus_state, fast_sync: bool = False):
        BaseService.__init__(self, name="consensus.reactor")
        self.con_s = consensus_state
        self.fast_sync = fast_sync
        self.evsw = None
        self._peer_states: dict[str, PeerState] = {}
        # what wake_gossip walks: replaced whole under _mtx when a peer
        # comes or goes and read without it, so the consensus receive
        # routine (which fires the events) never waits for a lock
        self._states: tuple[PeerState, ...] = ()
        self._mtx = threading.Lock()
        # has-vote-aware gossip dedup (round 20): when on, STATE-channel
        # HasVotes ensure the tracking arrays before applying (a fresh
        # height's first announcement window was silently dropped
        # before), last-commit-height HasVotes land, local part adds
        # broadcast HasBlockPart screens, and the vote pick loops hold
        # re-pushes of just-received votes for one gossip tick so the
        # announcements can set the mirror bits first (_relay_hold).
        # Off restores the pre-round-20 gossip for the before/after
        # bench.
        self.gossip_dedup = bool(
            getattr(consensus_state.config, "gossip_dedup", True)
        )
        # flat dedup accounting (consensus_gossip_* on both surfaces)
        self.has_votes_applied = 0
        self.part_announces_sent = 0
        self.part_announces_applied = 0
        # aggregate-format catchup accounting (round 22, docs/upgrade.md)
        self.agg_commits_sent = 0      # whole-commit catchup sends
        self.agg_commits_rejected = 0  # forged/sub-quorum screened out
        # the one gossip routine (conR.gossip, started in on_start), the
        # signal every mark sets, its stop, and where the next sweep's
        # order of peers starts
        self._gossip_signal = threading.Event()
        self._gossip_stop = threading.Event()
        self._gossip_thread: threading.Thread | None = None
        self._gossip_turn = 0
        # GOSSIP_COUNTERS: items sent; how the routine's waits ended — by
        # a signal, by a lazy-relay hold running out, by the idle
        # back-stop; and the sends to a peer that only a back-stop's look
        # found, which is an event nobody signalled (must stay near 0
        # beside gossip_sends)
        self.gossip_sends = 0
        self.gossip_wakes_event = 0
        self.gossip_wakes_hold = 0
        self.gossip_wakes_backstop = 0
        self.gossip_backstop_sends = 0
        # sweeps that looked at a peer, the peers they looked at, and the
        # items a full channel refused (retried after SEND_FULL_RETRY)
        self.gossip_sweeps = 0
        self.gossip_peer_looks = 0
        self.gossip_send_full = 0
        # has-vote announcements: messages sent (one a peer a flush and
        # key) and the votes they announced (one a vote a flush). Bits
        # over (announces / peers) is the mean burst
        self.gossip_announces_sent = 0
        self.gossip_announce_bits = 0
        # default_set_proposal fires no event: it calls this
        consensus_state.gossip_wake = self.wake_gossip
        # relay holds' ends and the announcement's instant: one thread
        self._wakes = _DeferredWakes()
        # smoothed seconds from our receipt of a vote to ANY peer's
        # announcement of it (None until one was seen); each peer keeps
        # its own beside it (PeerState.has_vote_lag)
        self._has_vote_lag: float | None = None
        # votes that entered our vote set and no peer was told of yet:
        # (height, round, type) -> [validators, mask]. One announcement
        # a key goes to every peer VOTE_RELAY_DELAY_MIN after the first
        # pending bit, or before the next step message, whichever is
        # first (_flush_has_votes). _announce_mtx guards the dict;
        # _announce_order is held over a whole flush, so that a step
        # broadcast waits for the bits a flush in flight is sending.
        self._announce_pending: dict[tuple[int, int, int], list[int]] = {}
        self._announce_mtx = threading.Lock()
        self._announce_order = threading.Lock()

    # -- wiring ------------------------------------------------------------

    def set_event_switch(self, evsw) -> None:
        """Subscribe broadcast triggers (reactor.go:321-337). The three
        events that change what OUR round state holds also mark every
        peer for the gossip routine."""
        self.evsw = evsw

        def on_step(_d):
            self.wake_gossip()
            self._broadcast_step()

        def on_vote(d):
            # a vote marks the peers' votes alone. Our own goes out now.
            # One we RECEIVED is held back from relay for _relay_hold
            # (its origin is fanning it out, and every peer says so with
            # HasVote): a look that finds it held is a wake for nothing,
            # so each peer is marked when its hold ends, once a burst.
            # The hold is each peer's own (_relay_delay).
            self._wake_for_vote(d.vote)
            self._note_has_vote(d.vote)

        def on_part(d):
            self.wake_data_gossip()
            self._broadcast_has_part(d)

        evsw.add_listener_for_event("conR", tev.EVENT_NEW_ROUND_STEP, on_step)
        evsw.add_listener_for_event("conR", tev.EVENT_VOTE, on_vote)
        evsw.add_listener_for_event(
            "conR", tev.EVENT_PROPOSAL_BLOCK_PART, on_part
        )
        evsw.add_listener_for_event(
            "conR",
            tev.EVENT_PROPOSAL_HEARTBEAT,
            lambda d: self._broadcast_heartbeat(d.heartbeat),
        )

    # -- Reactor interface -------------------------------------------------

    def get_channels(self) -> list[ChannelDescriptor]:
        from tendermint_tpu.types.params import MAX_BLOCK_PART_SIZE_BYTES

        # recv_message_capacity right-sized per channel (round 18): the
        # default 21 MiB is the BLOCK ceiling — on the consensus
        # channels the largest legal messages are a block part at the
        # params-validated MAX_BLOCK_PART_SIZE_BYTES bound (hex-doubled
        # + proof inside JSON — the DATA cap derives from that bound)
        # and sub-KiB steps/votes/bitarrays. Before this, an
        # oversized-frame peer could park 21 MiB of never-delivered
        # reassembly bytes on EVERY channel of every connection
        # (~147 MiB per hostile peer); now an over-claim errors the
        # peer at the right-sized bound.
        return [
            ChannelDescriptor(id=STATE_CHANNEL, priority=5, send_queue_capacity=100,
                              recv_message_capacity=1 << 16),
            ChannelDescriptor(
                id=DATA_CHANNEL, priority=10, send_queue_capacity=100,
                recv_buffer_capacity=50 * 4096,
                # 2x for hex + proof steps / envelope headroom
                recv_message_capacity=2 * MAX_BLOCK_PART_SIZE_BYTES + (1 << 16),
            ),
            ChannelDescriptor(
                id=VOTE_CHANNEL, priority=5, send_queue_capacity=100,
                recv_buffer_capacity=100 * 100,
                recv_message_capacity=1 << 16,
            ),
            ChannelDescriptor(
                id=VOTE_SET_BITS_CHANNEL, priority=1, send_queue_capacity=2,
                recv_buffer_capacity=1024,
                recv_message_capacity=1 << 16,
            ),
        ]

    def add_peer(self, peer) -> None:
        """Starts no thread: the peer joins what the one routine sweeps."""
        ps = PeerState(peer)
        peer.set(PEER_STATE_KEY, ps)
        ps.gossip.signal = self._gossip_signal
        with self._mtx:
            self._peer_states[peer.id()] = ps
            self._states = tuple(self._peer_states.values())
        ps.gossip.wake()
        # tell the new peer our current state
        if not self.fast_sync:
            for m in self._round_step_messages():
                peer.send(STATE_CHANNEL, _enc(m))

    def remove_peer(self, peer, reason) -> None:
        with self._mtx:
            ps = self._peer_states.pop(peer.id(), None)
            self._states = tuple(self._peer_states.values())
        if ps:
            ps.gossip.end()

    def wake_gossip(self) -> None:
        """Our own round state changed (a step, the proposal): every
        peer is looked at again now. O(peers) flag sets, no lock, never
        blocks — this runs on the consensus receive routine."""
        for ps in self._states:
            ps.gossip.wake()

    def wake_votes_gossip(self) -> None:
        """A vote entered our round state: every peer's votes alone."""
        for ps in self._states:
            ps.gossip.wake_votes()

    def wake_data_gossip(self) -> None:
        """A part entered our round state: every peer's data alone."""
        for ps in self._states:
            ps.gossip.wake_data()

    def _wake_for_vote(self, vote) -> None:
        """Mark each peer's votes when `vote` may go to it: now for our
        own vote, at the end of the peer's own hold for one we
        received. The mean hold applied goes onto the height's trace
        (aux relay_hold_s over relay_holds). Receive routine only."""
        got = self.con_s.vote_recv_mono.get(
            (vote.height, vote.round_, vote.type_, vote.validator_index)
        ) if self.gossip_dedup else None
        states = self._states
        if got is None or not states:
            self.wake_votes_gossip()
            return
        now = time.monotonic()
        later, total = [], 0.0
        for ps in states:
            delay = self._relay_delay(ps)
            total += delay
            if got + delay > now:
                later.append((ps.gossip.wake_votes, got + delay))
            else:
                ps.gossip.wake_votes()
        if later:
            self._wakes.at_many(later)
        self.con_s.trace.note("relay_hold_s", total / len(states))
        self.con_s.trace.note("relay_holds", 1)

    def _to_state(self, msg, peer) -> None:
        """Hand a peer's message to the state machine. A full queue holds
        this peer's reads back (its later messages wait, in order; the
        loop and every other peer go on) until the message fits, or
        PEER_PUT_TIMEOUT passes and add_peer_message drops it and counts
        the drop (consensus/state._enqueue_peer_msg)."""
        cs, pid = self.con_s, peer.id()
        if not cs.try_add_peer_message(msg, pid):
            hold_reads(lambda: cs.try_add_peer_message(msg, pid),
                       lambda: cs.add_peer_message(msg, pid),
                       cs.PEER_PUT_TIMEOUT)

    def receive(self, ch_id: int, peer, msg_bytes: bytes) -> None:
        """reactor.go:159-302."""
        if not self.is_running():
            return
        try:
            msg = _dec(msg_bytes)
        except (ValueError, KeyError, TypeError, UnicodeDecodeError) as exc:
            self.switch.stop_peer_for_error(peer, exc)
            return
        ps: PeerState | None = peer.get(PEER_STATE_KEY)
        if ps is None:
            return

        if ch_id == STATE_CHANNEL:
            # a wake follows what can make MORE sendable to this peer
            # (it entered our height or round, it asks for votes);
            # HasVote / HasBlockPart only ever take away: no wake
            if isinstance(msg, msgs.NewRoundStepMessage):
                ps.apply_new_round_step(msg)
                ps.gossip.wake()
            elif isinstance(msg, msgs.CommitStepMessage):
                ps.apply_commit_step(msg)
                ps.gossip.wake()
            elif isinstance(msg, msgs.HasVoteMessage):
                # the single form: what a peer of upstream's code sends
                if self.gossip_dedup:
                    self._ensure_vote_bit_arrays(ps)
                if ps.apply_has_vote(msg, allow_last_commit=self.gossip_dedup):
                    self.has_votes_applied += 1
                self._note_has_vote_lag(ps, self.con_s.vote_recv_mono.get(
                    (msg.height, msg.round_, msg.type_, msg.index)))
            elif isinstance(msg, msgs.HasVotesMessage):
                # a burst's worth of HasVote: one ensure, one lock, and
                # one sample of the lag, from the bit that waited longest
                if self.gossip_dedup:
                    self._ensure_vote_bit_arrays(ps)
                landed, fresh = ps.apply_has_votes(
                    msg, allow_last_commit=self.gossip_dedup)
                self.has_votes_applied += landed
                stamps = self.con_s.vote_recv_mono
                key = (msg.height, msg.round_, msg.type_)
                self._note_has_vote_lag(ps, min(
                    (t for t in (stamps.get(key + (i,)) for i in fresh)
                     if t is not None), default=None))
            elif isinstance(msg, msgs.HasBlockPartMessage):
                # round 20 part dedup screen: the peer announced a part
                # it holds — mark the mirror so gossip_data skips it
                # (applied regardless of our own knob: the information
                # is free and only ever REDUCES redundant sends)
                ps.set_has_proposal_block_part(msg.height, msg.round_, msg.index)
                self.part_announces_applied += 1
            elif isinstance(msg, msgs.ProposalHeartbeatMessage):
                self.con_s._fire(
                    tev.EVENT_PROPOSAL_HEARTBEAT,
                    tev.EventDataProposalHeartbeat(msg.heartbeat),
                )
            elif isinstance(msg, msgs.VoteSetMaj23Message):
                self._handle_vote_set_maj23(peer, ps, msg)
                ps.gossip.wake()
            else:
                self.switch.stop_peer_for_error(peer, f"bad state msg {type(msg)}")
        elif ch_id == DATA_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, msgs.ProposalMessage):
                ps.set_has_proposal(msg.proposal)
                self._to_state(msg, peer)
            elif isinstance(msg, msgs.ProposalPOLMessage):
                ps.apply_proposal_pol(msg)
                ps.gossip.wake()
            elif isinstance(msg, msgs.BlockPartMessage):
                ps.set_has_proposal_block_part(msg.height, msg.round_, msg.part.index)
                self._to_state(msg, peer)
            elif isinstance(msg, msgs.AggregateCommitMessage):
                if self._screen_agg_commit(peer, msg):
                    self._to_state(msg, peer)
            else:
                self.switch.stop_peer_for_error(peer, f"bad data msg {type(msg)}")
        elif ch_id == VOTE_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, msgs.VoteMessage):
                self._ensure_vote_bit_arrays(ps)
                ps.set_has_vote(
                    msg.vote.height, msg.vote.round_, msg.vote.type_,
                    msg.vote.validator_index,
                )
                self._to_state(msg, peer)
            else:
                self.switch.stop_peer_for_error(peer, f"bad vote msg {type(msg)}")
        elif ch_id == VOTE_SET_BITS_CHANNEL:
            if self.fast_sync:
                return
            if isinstance(msg, msgs.VoteSetBitsMessage):
                rs = self.con_s.get_round_state()
                if rs.height == msg.height and rs.votes is not None:
                    vs = (
                        rs.votes.prevotes(msg.round_)
                        if msg.type_ == VOTE_TYPE_PREVOTE
                        else rs.votes.precommits(msg.round_)
                    )
                    ours = vs.bit_array_by_block_id(msg.block_id) if vs else None
                else:
                    ours = None
                ps.apply_vote_set_bits(msg, ours)
                ps.gossip.wake()
            else:
                self.switch.stop_peer_for_error(peer, f"bad bits msg {type(msg)}")

    def _ensure_vote_bit_arrays(self, ps: PeerState) -> None:
        """Ensure the peer's tracking arrays at our height BEFORE a vote
        or (gossip_dedup) an announcement marks them: at a fresh height
        the mirror has none yet, and every HasVote in that first window
        used to vanish into the set_has_vote no-op (the biggest single
        source of the 2NxN duplicate pushes: peers kept picking votes
        the neighbor had announced long ago). The height-1 array tracks
        LastCommit votes, whose set can differ in size from the current
        one (reactor.go:291-296 uses cs.LastCommit.Size(), not
        cs.Validators.Size())."""
        rs = self.con_s.get_round_state()
        size = rs.validators.size() if rs.validators else 0
        last_size = rs.last_commit.size() if rs.last_commit else 0
        ps.ensure_vote_bit_arrays(rs.height, size)
        ps.ensure_vote_bit_arrays(rs.height - 1, last_size)

    def _note_has_vote_lag(self, ps: PeerState, got: float | None) -> None:
        """How long after WE received a vote (`got`, its vote_recv_mono
        stamp) a peer says it has it: what a relay's hold has to outlast
        (_relay_delay), kept for that peer and over all of them."""
        lag = ps.note_has_vote_lag(got)
        if lag is not None:
            self._has_vote_lag = _smoothed(self._has_vote_lag, lag)

    def _screen_agg_commit(self, peer, msg: msgs.AggregateCommitMessage) -> bool:
        """Verify a received aggregate catchup commit on the peer thread
        BEFORE it reaches the consensus queue: a forged or sub-quorum
        aggregate is a peer error (stop_peer_for_error) — the aggregate
        form makes the whole commit one signature check, so the screen
        costs one gateway batch, not N serial verifies. True = enqueue
        for the consensus thread (which re-verifies: WAL replay must
        re-derive the verdict)."""
        rs = self.con_s.get_round_state()
        if msg.height != msg.commit.height():
            self.switch.stop_peer_for_error(
                peer, "aggregate commit message height mismatch"
            )
            return False
        if msg.height != rs.height or rs.validators is None:
            return False  # stale (we moved on) or not ready — drop quietly
        err = msg.commit.validate_basic()
        if err is None:
            try:
                msg.commit.verify(self.con_s.state.chain_id, rs.validators)
            except CommitError as exc:
                err = str(exc)
        if err is not None:
            self.agg_commits_rejected += 1
            fr = getattr(self.con_s, "flightrec", None)
            if fr is not None:
                fr.record("agg_commit_reject", height=msg.height,
                          err=err, peer=_peer_label(peer))
            self.switch.stop_peer_for_error(
                peer, f"bad aggregate commit: {err}"
            )
            return False
        return True

    def _handle_vote_set_maj23(self, peer, ps: PeerState, msg: msgs.VoteSetMaj23Message) -> None:
        """reactor.go:230-263: record the claim, respond with our bits."""
        rs = self.con_s.get_round_state()
        if rs.height != msg.height or rs.votes is None:
            return
        rs.votes.set_peer_maj23(msg.round_, msg.type_, peer.id(), msg.block_id)
        vs = (
            rs.votes.prevotes(msg.round_)
            if msg.type_ == VOTE_TYPE_PREVOTE
            else rs.votes.precommits(msg.round_)
        )
        ours = vs.bit_array_by_block_id(msg.block_id) if vs else None
        if ours is None:
            return
        peer.try_send(
            VOTE_SET_BITS_CHANNEL,
            _enc(
                msgs.VoteSetBitsMessage(
                    msg.height, msg.round_, msg.type_, msg.block_id, ours
                )
            ),
        )

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        self._start_gossip()
        if not self.fast_sync:
            self.con_s.start()

    def _start_gossip(self) -> None:
        self._gossip_thread = threading.Thread(
            target=self._gossip_routine, daemon=True, name="conR.gossip")
        self._gossip_thread.start()

    def on_stop(self) -> None:
        self.con_s.stop()
        self._wakes.stop()
        self._gossip_stop.set()
        self._gossip_signal.set()  # the stop must also end the wait

    def switch_to_consensus(self, state) -> None:
        """Fast sync complete (reactor.go:78-90). Note: update BEFORE
        reconstruct (the NewConsensusState ordering, state.go:327-330) —
        the reactor's reconstruct-first ordering in the reference lets
        updateToState clobber the freshly rebuilt LastCommit to nil,
        which breaks proposing at the switch height."""
        self.logger.info("switching to consensus at height %d", state.last_block_height + 1)
        self.con_s.update_to_state(state.copy())
        if state.last_block_height > 0:
            self.con_s.reconstruct_last_commit(state)
        self.fast_sync = False
        self.con_s.start()

    # -- broadcasts --------------------------------------------------------

    def _round_step_messages(self) -> list:
        rs = self.con_s.get_round_state()
        out = [
            msgs.NewRoundStepMessage(
                height=rs.height,
                round_=rs.round_,
                step=rs.step,
                seconds_since_start_time=int(time.time() - rs.start_time),
                last_commit_round=rs.last_commit.round_ if rs.last_commit else -1,
            )
        ]
        if rs.step == RoundStep.COMMIT and rs.proposal_block_parts is not None:
            out.append(
                msgs.CommitStepMessage(
                    height=rs.height,
                    block_parts_header=rs.proposal_block_parts.header(),
                    block_parts=rs.proposal_block_parts.bit_array(),
                )
            )
        return out

    def _broadcast_step(self) -> None:
        if not hasattr(self, "switch") or self.switch is None:
            return
        # a step message never overtakes the bits of the round it ends:
        # a peer's apply_new_round_step resets the arrays they belong to
        self._flush_has_votes()
        for m in self._round_step_messages():
            self.switch.broadcast(STATE_CHANNEL, _enc(m))

    def _note_has_vote(self, vote) -> None:
        """A vote entered our vote set: its bit waits for the burst's
        announcement. The first pending bit arms the one timer; the
        instant is the shortest hold a peer's relay can have
        (VOTE_RELAY_DELAY_MIN), and a peer acts on its mirror of us only
        when a hold of its own ends, so the wait cannot lose the race
        the announcement exists to win. It is NOT tied to _relay_delay:
        the lag a peer measures contains this wait, and the two would
        climb to VOTE_RELAY_DELAY_MAX together."""
        if not hasattr(self, "switch") or self.switch is None:
            return
        rs = self.con_s.get_round_state()
        if vote.height == rs.height:
            vals = rs.validators
        elif vote.height == rs.height - 1:
            vals = rs.last_commit  # the size a peer's last_commit array has
        else:
            return
        if not vals:
            return
        key = (vote.height, vote.round_, vote.type_)
        with self._announce_mtx:
            first = not self._announce_pending
            slot = self._announce_pending.setdefault(key, [vals.size(), 0])
            slot[1] |= 1 << vote.validator_index
        if first:
            self._wakes.at(self._flush_has_votes,
                           time.monotonic() + VOTE_RELAY_DELAY_MIN)

    def _flush_has_votes(self) -> None:
        """Every key's pending bits go out as one HasVotesMessage to
        every peer. try_send like every announcement: a full STATE queue
        drops it (the votes still dedup the hard way), it never blocks
        the consensus thread that flushes before a step."""
        with self._announce_order:
            with self._announce_mtx:
                pending, self._announce_pending = self._announce_pending, {}
            peers = len(self._states)
            for (height, round_, type_), (size, mask) in pending.items():
                votes = BitArray.from_int(size, mask)
                self.switch.broadcast(STATE_CHANNEL, _enc(msgs.HasVotesMessage(
                    height=height, round_=round_, type_=type_, votes=votes)))
                self.gossip_announces_sent += peers
                self.gossip_announce_bits += votes.num_true_bits()

    def _broadcast_has_part(self, data) -> None:
        """Round 20: a part landed in OUR part-set — announce it so
        peers' mirrors mark the bit and their gossip_data loops stop
        picking it for us. try_send like the maj23 path: a full STATE
        queue drops the announcement (the part relay itself still dedups
        the hard way), it must never block the consensus thread firing
        the event."""
        if not self.gossip_dedup:
            return
        if not hasattr(self, "switch") or self.switch is None:
            return
        msg = msgs.HasBlockPartMessage(
            height=data.height, round_=data.round_, index=data.index
        )
        self.switch.broadcast(STATE_CHANNEL, _enc(msg))
        self.part_announces_sent += 1

    def _broadcast_heartbeat(self, heartbeat) -> None:
        if not hasattr(self, "switch") or self.switch is None:
            return
        self.switch.broadcast(
            STATE_CHANNEL, _enc(msgs.ProposalHeartbeatMessage(heartbeat))
        )

    # -- the one gossip routine ---------------------------------------------

    def _gossip_routine(self) -> None:
        """gossipData, gossipVotes and queryMaj23 for ALL peers. The
        signal is cleared and the marks are taken BEFORE the round state
        is read, so whatever lands after that ends the next wait at once.
        The wait lasts until the signal, until the earliest lazy-relay
        hold of any peer ends, or for the back-stop, whichever is first:
        PEER_GOSSIP_SLEEP, doubled for every back-stop in a row that
        found nothing, up to GOSSIP_BACKSTOP_MAX_SLEEPS of them. A
        back-stop looks at every peer; an event never puts it off, it
        only brings it back to one PEER_GOSSIP_SLEEP from now."""
        signal, stop = self._gossip_signal, self._gossip_stop
        idle = 0          # back-stops in a row that found nothing to send
        ran_out = False   # the last wait ended on the back-stop
        now = time.monotonic()
        backstop_at = now + PEER_GOSSIP_SLEEP
        maj23_at = now + PEER_QUERY_MAJ23_SLEEP
        while self.is_running():
            if self.fast_sync:
                if stop.wait(PEER_GOSSIP_SLEEP):
                    return
                continue
            signal.clear()
            # the stop is read AFTER the clear, like the round state: a
            # stop that lands from here on still finds the signal to set
            if stop.is_set():
                return
            now = time.monotonic()
            try:
                if now >= maj23_at:
                    maj23_at = now + PEER_QUERY_MAJ23_SLEEP
                    self._query_maj23_sweep()
                sent, hold_at = self._gossip_sweep(everyone=ran_out)
            except Exception:  # noqa: BLE001 — the one routine all peers have
                self.logger.exception("gossip sweep")
                sent, hold_at = 0, None
            now = time.monotonic()
            if ran_out:
                ran_out = False
                idle = 0 if sent else min(idle + 1, GOSSIP_BACKSTOP_MAX_SLEEPS)
                backstop_at = now + PEER_GOSSIP_SLEEP * min(
                    2 ** idle, GOSSIP_BACKSTOP_MAX_SLEEPS)
            until = min(backstop_at, maj23_at)
            held = hold_at is not None and hold_at < until
            if signal.wait(max(0.0, (hold_at if held else until) - now)):
                self.gossip_wakes_event += 1
            elif held:
                self.gossip_wakes_hold += 1
            elif until == backstop_at:
                self.gossip_wakes_backstop += 1
                ran_out = True
                continue
            else:
                continue  # the maj23 claims are due, and nothing else
            idle = 0
            backstop_at = min(backstop_at,
                              time.monotonic() + PEER_GOSSIP_SLEEP)

    def _gossip_sweep(self, everyone: bool = False) -> tuple[int, float | None]:
        """One look at every marked peer (at all of them for a back-stop),
        in an order that starts one peer further each sweep. Returns the
        items sent and the instant the earliest relay hold of ANY peer
        ends (None: no vote is held). First the peers served from the
        round state, each until it needs nothing more or its channel is
        full; then ONE stored item for each peer that lags behind our
        height, so that a node catching up holds no vote round back."""
        states = self._states
        n = len(states)
        now = time.monotonic()
        looks = []
        start = self._gossip_turn % n if n else 0
        for i in range(n):
            ps = states[(start + i) % n]
            g = ps.gossip
            data = votes = told = False
            if g.data:
                g.data = False
                data = told = True
            if g.votes:
                g.votes = False
                votes = told = True
            if g.hold_until is not None and g.hold_until <= now:
                votes = told = True
            if told or everyone:
                looks.append((ps, data or everyone, votes or everyone, told))
        sent = 0
        if looks:
            self._gossip_turn += 1
            self.gossip_sweeps += 1
            self.gossip_peer_looks += len(looks)
            rs = self.con_s.get_round_state()  # once, AFTER the marks
            once: dict = {}   # an item's encoding, made once a sweep
            stored = []
            for ps, data, votes, told in looks:
                k = self._guarded(self._gossip_peer, ps, rs, once, data,
                                  votes, stored)
                sent += k
                if not told:
                    self.gossip_backstop_sends += k
            for ps, duties in stored:
                sent += self._guarded(self._gossip_peer_stored, ps, rs,
                                      once, duties)
            self.gossip_sends += sent
        holds = [ps.gossip.hold_until for ps in states
                 if ps.gossip.hold_until is not None]
        return sent, min(holds, default=None)

    def _gossip_peer(self, ps: PeerState, rs, once: dict, data: bool,
                     votes: bool, stored: list) -> int:
        """Everything the round state holds for one peer, its data and
        then its votes, each as (its look, the mark that asks for it
        again); a peer that needs the store goes onto `stored` with the
        duties that said so."""
        g = ps.gossip
        duties = []
        if data:
            duties.append((self._gossip_data_pass, g.wake_data))
        if votes:
            g.hold_until = None
            duties.append((self._gossip_votes_pass, g.wake_votes))
        sent, behind = 0, []
        for look, again in duties:
            while not g.stopped:
                got, hold_s = look(ps, rs, once)
                if got == _SENT:
                    sent += 1
                    continue
                if got == _STORE:
                    behind.append((look, again))
                elif got == _FULL:
                    self._send_full(again)
                elif hold_s is not None:
                    g.hold_until = time.monotonic() + hold_s
                break
        if behind:
            stored.append((ps, behind))
        return sent

    def _gossip_peer_stored(self, ps: PeerState, rs, once: dict,
                            duties: list) -> int:
        """ONE item of a committed height for a peer behind ours, read
        from the store; given one, the peer stays marked for the next."""
        for look, again in duties:
            if ps.gossip.stopped:
                break
            got, _hold = look(ps, rs, once, stored=True)
            if got == _SENT:
                for _look, mark in duties:
                    mark()
                return 1
            if got == _FULL:
                self._send_full(again)
        return 0

    def _guarded(self, look, ps: PeerState, *args) -> int:
        """One peer's fault (a connection that breaks under the sweep's
        hands) ends no other peer's gossip."""
        try:
            return look(ps, *args)
        except Exception:  # noqa: BLE001
            self.logger.exception("gossip to peer %s", _peer_label(ps.peer))
            return 0

    def _send_full(self, again) -> None:
        """A peer's channel refused an item: its mirror bit stays clear,
        and `again` marks the peer once its connection has flushed."""
        self.gossip_send_full += 1
        self._wakes.at(again, time.monotonic() + SEND_FULL_RETRY)

    def _note_own_send(self, height: int, key: tuple) -> None:
        """The first send of an item of OUR OWN origin (our proposal, its
        parts, our votes): the time since it entered our round state
        (state.own_entered_mono) goes onto that height's trace as the
        aux note gossip_send_lag_s, summed over the height's items."""
        entered = getattr(self.con_s, "own_entered_mono", None)
        if not entered:
            return
        t = entered.pop(key, None)
        if t is not None:
            self.con_s.trace.note_overlap(
                height, "gossip_send_lag_s", time.monotonic() - t
            )

    # -- gossip_data (reactor.go:413-535) ----------------------------------

    def _gossip_data_pass(self, ps: PeerState, rs, once: dict,
                          stored: bool = False) -> tuple[int, None]:
        """One look of gossip_data at one peer: at most ONE item."""
        return self._send_data(ps.peer, ps, rs, once, stored), None

    def _send_data(self, peer, ps: PeerState, rs, once: dict,
                   stored: bool) -> int:
        prs = ps.get_round_state()
        parts = rs.proposal_block_parts
        # 1. send a block part the peer lacks
        if (
            parts is not None
            and prs.proposal_block_parts is not None
            and rs.height == prs.height
            and rs.round_ == prs.round_
        ):
            needed = parts.bit_array().sub(prs.proposal_block_parts)
            if not needed.is_empty():
                index, ok = needed.pick_random()
                if ok:
                    key = ("part", prs.height, prs.round_, index)
                    raw = _enc_once(once, key, msgs.BlockPartMessage,
                                    prs.height, prs.round_, parts.get_part(index))
                    if not peer.try_send(DATA_CHANNEL, raw):
                        return _FULL
                    ps.set_has_proposal_block_part(prs.height, prs.round_, index)
                    self._note_own_send(prs.height, key)
                    return _SENT
        # 2. peer is on an older height: catch them up from the store
        if prs.height != 0 and rs.height > prs.height:
            if not stored:
                return _STORE
            return self._gossip_data_catchup(peer, ps, prs)
        # 3. send the proposal (+POL) if the peer doesn't have it
        proposal = rs.proposal
        if (
            rs.height == prs.height
            and rs.round_ == prs.round_
            and proposal is not None
            and not prs.proposal
        ):
            key = ("proposal", prs.height, prs.round_)
            raw = _enc_once(once, key, msgs.ProposalMessage, proposal)
            if not peer.try_send(DATA_CHANNEL, raw):
                return _FULL
            ps.set_has_proposal(proposal)
            self._note_own_send(prs.height, key)
            if 0 <= proposal.pol_round < rs.round_ and rs.votes is not None:
                pol = rs.votes.prevotes(proposal.pol_round)
                if pol is not None:
                    peer.try_send(DATA_CHANNEL, _enc(msgs.ProposalPOLMessage(
                        rs.height, proposal.pol_round, pol.bit_array())))
            return _SENT
        return _IDLE

    def _gossip_data_catchup(self, peer, ps: PeerState, prs: PeerRoundState) -> int:
        """Send a part of a committed block (reactor.go:494-535)."""
        store = getattr(self.con_s, "block_store", None)
        if store is None:
            return _IDLE
        meta = store.load_block_meta(prs.height)
        if meta is None:
            return _IDLE
        if prs.proposal_block_parts is None:
            # init from the committed block's part-set header
            ps_header = meta.block_id.parts_header
            ps.apply_commit_step(
                msgs.CommitStepMessage(
                    height=prs.height,
                    block_parts_header=ps_header,
                    block_parts=BitArray(ps_header.total),
                )
            )
            prs = ps.get_round_state()
            if prs.proposal_block_parts is None:
                return _IDLE  # the peer moved on meanwhile
        if meta.block_id.parts_header != prs.proposal_block_parts_header:
            return _IDLE
        needed = prs.proposal_block_parts.not_()
        if needed.is_empty():
            return _IDLE
        index, ok = needed.pick_random()
        if not ok:
            return _IDLE
        part = store.load_block_part(prs.height, index)
        if part is None:
            return _IDLE
        msg = msgs.BlockPartMessage(prs.height, prs.round_, part)
        if not peer.try_send(DATA_CHANNEL, _enc(msg)):
            return _FULL
        ps.set_has_proposal_block_part(prs.height, prs.round_, index)
        return _SENT

    # -- gossip_votes (reactor.go:537-645) ---------------------------------

    def _gossip_votes_pass(self, ps: PeerState, rs, once: dict,
                           stored: bool = False) -> tuple[int, float | None]:
        """One look of gossip_votes at one peer: at most ONE vote."""
        if rs.validators is not None:
            ps.ensure_vote_bit_arrays(rs.height, rs.validators.size())
            # a peer lagging one height needs last-commit bit arrays
            # before pick_vote_to_send can track what it has
            if rs.last_validators is not None:
                ps.ensure_vote_bit_arrays(
                    rs.height - 1, rs.last_validators.size()
                )
        return self._pick_and_send_vote(
            ps.peer, ps, rs, ps.get_round_state(), once, stored)

    def _send_vote(self, peer, ps: PeerState, vote, raw: bytes | None = None) -> bool:
        """Send one vote (`raw`: its encoding, where the sweep made it
        already) and, ONLY on success, mark the peer as having it (the
        vote carries its own coordinates). A refused send leaves the bit
        clear so the gossip routine retries it later — and counts on the
        per-peer failure series, so a wedge shows up as picks outrunning
        sends instead of a frozen height vector."""
        ps.m_vote_picks.inc()
        if raw is None:
            raw = _enc(msgs.VoteMessage(vote))
        if peer.try_send(VOTE_CHANNEL, raw):
            ps.set_has_vote(
                vote.height, vote.round_, vote.type_, vote.validator_index
            )
            ps.m_vote_sends.inc()
            return True
        ps.m_vote_send_failures.inc()
        fr = getattr(getattr(self, "con_s", None), "flightrec", None)
        if fr is not None:
            # picks-without-sends IS the gossip-stall signature a wedge
            # dump must carry (node/flightrec.py)
            fr.record("gossip_send_fail", peer=_peer_label(peer))
        return False

    def _relay_delay(self, ps: PeerState) -> float:
        """The lazy-relay hold of votes for ONE peer: RTT-adaptive from
        that link's own ping samples (adaptive_relay_delay; the
        VOTE_RELAY_DELAY constant before the first, and for harness peers
        that measure none). A net across datacenters has links of 1 ms
        and of 312 ms at one node: the announcement that makes a relay
        needless comes back over the link the relay would take."""
        hold = adaptive_relay_delay(ps.rtt_s())
        # a ping says what the link costs, not how far behind a peer's
        # process runs: on a host with fewer cores than validators the
        # HasVotes came 0.1-0.3 s after the vote, every relay fired
        # inside that, and a node received 3.5 duplicates for each vote
        # it accepted (PERF.md, PR 27). So the hold also outlasts twice
        # the lag the announcements are seen to have: this peer's own
        # (across datacenters it is the link's round trip and a little)
        # or all peers' (on one host a peer's lag is the scheduler's
        # mood of the moment, and its own average alone let twice the
        # duplicates through: PERF.md, PR 32), whichever is longer.
        lags = [x for x in (ps.has_vote_lag, self._has_vote_lag)
                if x is not None]
        if lags:
            hold = min(VOTE_RELAY_DELAY_MAX, max(hold, 2.0 * max(lags)))
        return hold

    def _relay_hold(self, vote, delay: float) -> float:
        """The lazy-relay screen: seconds a re-push of `vote` is still
        held (0.0: relay now), `delay` being the peer's _relay_delay. A
        vote we received less than that ago is held; unstamped votes —
        our own, and store-backed catchup commits — relay immediately. A
        held vote stays pickable and goes out when its hold ends if the
        peer's mirror bit is still clear then."""
        if not self.gossip_dedup:
            return 0.0
        t = self.con_s.vote_recv_mono.get(
            (vote.height, vote.round_, vote.type_, vote.validator_index)
        )
        if t is None:
            return 0.0
        return max(0.0, t + delay - time.monotonic())

    def _pick_and_send_vote(self, peer, ps: PeerState, rs, prs: PeerRoundState,
                            once: dict, stored: bool = False,
                            ) -> tuple[int, float | None]:
        """One needed vote, if any (reactor.go:609-645 gossipVotesForHeight
        + same-height/lastCommit/catchup cases). Returns (what it came
        to, hold_s): when nothing went out and votes the peer needs sit
        behind the lazy-relay screen, hold_s is the time until the
        earliest of them may go — what the routine waits, instead of a
        whole back-stop on top of the hold. The stored seen-commit of a
        peer far behind is read only with `stored` (_STORE asks for it)."""
        hold_s: float | None = None
        delay = self._relay_delay(ps) if self.gossip_dedup else 0.0

        def hold_of(vote) -> float:
            nonlocal hold_s
            left = self._relay_hold(vote, delay)
            if left > 0.0 and (hold_s is None or left < hold_s):
                hold_s = left
            return left

        def send(vote) -> tuple[int, None]:
            key = (vote.height, vote.round_, vote.type_, vote.validator_index)
            raw = _enc_once(once, key, msgs.VoteMessage, vote)
            if not self._send_vote(peer, ps, vote, raw):
                return _FULL, None
            self._note_own_send(vote.height, key)
            return _SENT, None

        votes = rs.votes
        # same height
        if rs.height == prs.height and votes is not None:
            # peer is lagging in rounds: their POL prevotes
            if prs.step <= RoundStep.PROPOSE and prs.round_ != -1 and \
               prs.round_ <= rs.round_ and prs.proposal_pol_round != -1:
                pol = votes.prevotes(prs.proposal_pol_round)
                vote = ps.pick_vote_to_send(pol, hold_of) if pol else None
                if vote is not None:
                    return send(vote)
            if prs.step <= RoundStep.PREVOTE_WAIT and prs.round_ != -1 and \
               prs.round_ <= rs.round_:
                vote = ps.pick_vote_to_send(
                    votes.prevotes(prs.round_), hold_of
                )
                if vote is not None:
                    return send(vote)
            if prs.step <= RoundStep.PRECOMMIT_WAIT and prs.round_ != -1 and \
               prs.round_ <= rs.round_:
                vote = ps.pick_vote_to_send(
                    votes.precommits(prs.round_), hold_of
                )
                if vote is not None:
                    return send(vote)
            if prs.proposal_pol_round != -1:
                pol = votes.prevotes(prs.proposal_pol_round)
                vote = ps.pick_vote_to_send(pol, hold_of) if pol else None
                if vote is not None:
                    return send(vote)
        # peer is at our last height: send from our last commit. The
        # peer's CURRENT round usually raced past the commit round (it
        # entered a timeout round precisely because the commit votes
        # didn't reach it), so its prevote/precommit arrays track the
        # wrong round and _get_vote_bit_array would find NOTHING —
        # ensure the catchup-commit tracking array at the commit's round
        # first, exactly like the >= +2 stored-commit branch below. This
        # hole wedged 2-2 height splits permanently: the two ahead nodes
        # couldn't advance (no quorum at the new height), so the +2
        # branch never engaged, and the laggards never saw the commit.
        last_commit = rs.last_commit
        if rs.height == prs.height + 1 and last_commit is not None:
            if isinstance(last_commit, AggregateLastCommit):
                # our last commit exists only in aggregate form (we
                # ourselves finalized from a proof): no per-vote sends
                # possible — ship the whole commit
                return self._send_agg_commit(
                    peer, ps, prs.height, last_commit.agg
                ), None
            if rs.last_validators is not None:
                ps.ensure_catchup_commit_round(
                    prs.height, last_commit.round_,
                    rs.last_validators.size(),
                )
                prs = ps.get_round_state()
            vote = ps.pick_vote_to_send(last_commit, hold_of)
            if vote is not None:
                return send(vote)
        # peer is far behind: catch up with the stored seen-commit
        if rs.height >= prs.height + 2 and prs.height > 0:
            store = getattr(self.con_s, "block_store", None)
            if store is not None:
                if not stored:
                    return _STORE, None
                commit = store.load_block_commit(prs.height)
                if commit is not None:
                    if commit_is_aggregate(commit):
                        # the stored commit IS the aggregate (post-flip
                        # heights, docs/upgrade.md): per-vote catchup is
                        # impossible — one AggregateCommitMessage carries
                        # the whole quorum
                        return self._send_agg_commit(
                            peer, ps, prs.height, commit
                        ), None
                    ps.ensure_catchup_commit_round(
                        prs.height, commit.round_(), len(commit.precommits)
                    )
                    vote = self._pick_commit_vote_to_send(ps, prs, commit)
                    if vote is not None:
                        return (_SENT if self._send_vote(peer, ps, vote)
                                else _FULL), None
        return _IDLE, hold_s

    def _send_agg_commit(self, peer, ps: PeerState, height: int, agg) -> int:
        """One whole-commit catchup send, per-peer deduplicated: the
        aggregate replaces N per-vote sends, so it goes out once per
        lagging height (re-armed after a short hold in case the frame
        was lost). Marks only on successful send, like _send_vote."""
        if not ps.agg_commit_due(height):
            return _IDLE
        msg = msgs.AggregateCommitMessage(height, agg)
        if peer.try_send(DATA_CHANNEL, _enc(msg)):
            ps.mark_agg_commit_sent(height)
            ps.m_catchup_commits.inc()
            self.agg_commits_sent += 1
            return _SENT
        fr = getattr(self.con_s, "flightrec", None)
        if fr is not None:
            fr.record("gossip_send_fail", peer=_peer_label(peer))
        return _FULL

    def _pick_commit_vote_to_send(self, ps: PeerState, prs: PeerRoundState, commit):
        """Catch-up votes come from a Commit, not a VoteSet. Like
        pick_vote_to_send, this does NOT mark — _send_vote marks only
        after the send actually succeeds."""
        with ps._mtx:
            ba = ps._get_vote_bit_array(prs.height, commit.round_(), VOTE_TYPE_PRECOMMIT)
            if ba is None:
                return None
            have = BitArray.from_indices(
                len(commit.precommits),
                [i for i, pc in enumerate(commit.precommits) if pc is not None],
            )
            needed = have.sub(ba)
            if needed.is_empty():
                return None
            index, ok = needed.pick_random()
            if not ok:
                return None
            return commit.precommits[index]

    # -- query_maj23 (reactor.go:647-739) ----------------------------------

    def _query_maj23_sweep(self) -> None:
        """Every PEER_QUERY_MAJ23_SLEEP, every peer at our height is told
        which block we saw +2/3 of the votes for, in its own round."""
        rs = self.con_s.get_round_state()
        votes = rs.votes
        if votes is None:
            return
        once: dict = {}  # a claim's encoding, made once for all peers
        for ps in self._states:
            if ps.gossip.stopped:
                continue
            prs = ps.get_round_state()
            if rs.height != prs.height:
                continue
            sends = []
            prevotes = votes.prevotes(prs.round_)
            if prevotes is not None:
                maj = prevotes.two_thirds_majority()
                if maj is not None:
                    sends.append((prs.round_, VOTE_TYPE_PREVOTE, maj))
            precommits = votes.precommits(prs.round_)
            if precommits is not None:
                maj = precommits.two_thirds_majority()
                if maj is not None:
                    sends.append((prs.round_, VOTE_TYPE_PRECOMMIT, maj))
            if prs.proposal_pol_round >= 0:
                pol = votes.prevotes(prs.proposal_pol_round)
                if pol is not None:
                    maj = pol.two_thirds_majority()
                    if maj is not None:
                        sends.append((prs.proposal_pol_round, VOTE_TYPE_PREVOTE, maj))
            for round_, type_, block_id in sends:
                # maj23 claims ride the STATE channel, where receive()
                # handles them (reference reactor.go:662 sends these on
                # StateChannel too)
                ps.peer.try_send(STATE_CHANNEL, _enc_once(
                    once, (round_, type_), msgs.VoteSetMaj23Message,
                    prs.height, round_, type_, block_id))
