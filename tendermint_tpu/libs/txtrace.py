"""Transaction-lifecycle tracing (round 17, PR 37; docs/observability.md).

The per-height consensus traces (round 11) and the fleet timelines
(round 15) answer "how is the node/fleet doing"; this module answers
"where did ONE write spend its time", from the port it arrived on to its
reply, on every node that touched it. A traced tx is stamped with a
wall-clock instant at each lifecycle stage it crosses —

    rpc_ingress     check_tx entry (RPC submit, or gossip arrival on a
                    replica — the record carries the source)
    gate_dispatch   its signature-gate batch went to the verifier (the
                    record keeps that call's daemon rid, `gate_rid`)
    sig_gate        the gate's verdict for the batch landed
    mempool_admit   the app's grouped CheckTx for the batch answered
    p2p_broadcast   first gossip send to any peer succeeded
    reap            THIS node reaped it into the block that committed it
                    (the proposer of that block only; the last reap, where
                    a round re-proposes)
    proposal        the proposal block carrying it is whole here
    block_commit    the block carrying it finalized (stage 1: the WAL
                    marker is down; the record learns its height here)
    apply           the block's deferred/serial apply completed
    event_delivery  the tx's DeliverTx event flushed to subscribers
    rpc_reply       broadcast_tx_commit returns its answer (the node the
                    write was sent to)

Stamps are keep-first except `reap` (keep-last), absolute epoch seconds —
the SAME convention as the round-15 gossip arrival marks and the
daemon's call records (`time.time_ns`), so one tx hash joins ACROSS nodes
and against the daemon's records with no offset to estimate.

Sampling: a tx is traced iff crc32 of its first 96 bytes (a signed tx's
pubkey and signature) is 0 mod N — the same decision on EVERY node, so
the node a write was sent to and the proposer that reaped it trace the
same writes. The untraced hot path pays one C-level checksum at check_tx.

    TENDERMINT_TXTRACE_SAMPLE_N    (4)    trace 1 in N txs (0 = off)
    TENDERMINT_TXTRACE_MAX_ACTIVE  (256)  in-flight trace bound — beyond
                                          it the oldest active trace is
                                          sealed as "evicted"
    TENDERMINT_TXTRACE_RING        (2048) completed-trace ring
    TENDERMINT_TXTRACE_DISABLE     (0)    kill switch

A trace seals at event_delivery, except one whose submitter waits in
broadcast_tx_commit (`expect_reply`): that one seals at rpc_reply. A
sealed trace drops its tx bytes and keeps the hash. Batch-granular stamp
sites (`stamp_present`, `stamp_gate_dispatch`) cost one dict.get per
batch tx only while traces are in flight.

Metrics (materialized on the node registry by node/telemetry.py):
``tx_stage_seconds{stage}`` — span from the previous stamped instant —
plus the end-to-end ``tx_commit_latency_seconds`` (rpc_ingress ->
block_commit) and ``tx_visible_latency_seconds`` (rpc_ingress ->
event_delivery) histograms, observed once per sealed trace. The spans
TELESCOPE: they follow the stamps in time order, so for any sealed trace
the spans through block_commit sum EXACTLY to its commit latency.

Served by the ``tx_trace`` RPC (completed ring + in-flight actives), the
``python -m tendermint_tpu.ops.txtrace`` cross-node CLI, and the flight
recorder's stop dump (``tx_traces``), which the benchmark reads.

A read's road: an ``abci_query`` from the RPC port to its reply,
keyed by its JSON-RPC ``id``, stamped on the thread that serves it —

    rpc_ingress     the handler has the request (its id parsed)
    app_lock        the proxy's app mutex is held (abci/client.py)
    proved          the value and its TreeProof are in hand: the tree's
                    lock and the walk are done (abci/apps/kvstore.py)
    rpc_reply       the reply is written

A read is sampled by the node's own count of reads, 1 in the same N (a
record's key would trace a hot record always or never), under the same
kill switch. Sealed traces go to a ring of 4x TENDERMINT_TXTRACE_RING
(on a read-mostly chain a node answers many reads for each write), the
``query_stage_seconds{stage}`` histogram, and the stop dump's
``query_traces``.
"""

from __future__ import annotations

import itertools
import threading
import time
import zlib
from collections import deque

from tendermint_tpu.libs.envknob import env_number as _env_number

# canonical stage order (display, docs/observability.md diagram, and the
# tie-break of two stamps at one instant)
STAGES = (
    "rpc_ingress", "gate_dispatch", "sig_gate", "mempool_admit",
    "p2p_broadcast", "reap", "proposal", "block_commit", "apply",
    "event_delivery", "rpc_reply",
)
_RANK = {s: i for i, s in enumerate(STAGES)}
READ_STAGES = ("rpc_ingress", "app_lock", "proved", "rpc_reply")
_READ_RANK = {s: i for i, s in enumerate(READ_STAGES)}
# the bytes the sample rule hashes: a signed tx's pubkey and signature
SAMPLE_BYTES = 96

_hist_attr = "_txtrace_family_cache"


def in_sample(tx: bytes, n: int) -> bool:
    """The sample rule, the same on every node: 1 in n txs by the crc32
    of their first SAMPLE_BYTES bytes (n <= 0: none)."""
    return n > 0 and zlib.crc32(tx[:SAMPLE_BYTES]) % n == 0


def _spans(stamps: dict, rank: dict) -> dict[str, float]:
    """Seconds since the PREVIOUS stamped instant, for each stamped stage
    but the first, in time order (ties in `rank` order): they telescope
    to the last stamp less the first."""
    out: dict[str, float] = {}
    prev = None
    for t, _rank, stage in sorted(
            (t, rank.get(s, len(rank)), s) for s, t in stamps.items()):
        if prev is not None:
            out[stage] = t - prev
        prev = t
    return out


def txtrace_hists(reg=None) -> dict:
    """Create-or-get the tx-lifecycle histogram families on `reg`
    (default: the process-wide registry). Cached on the registry object
    like p2p/telemetry.peer_metrics so seals pay one attribute read."""
    from tendermint_tpu.libs import telemetry

    if reg is None:
        reg = telemetry.default_registry()
    cached = getattr(reg, _hist_attr, None)
    if cached is not None:
        return cached
    fams = {
        "stage": reg.histogram(
            "tx_stage_seconds",
            "per-tx span from the previous stamped lifecycle stage to "
            "this one (sampled txs only)",
            labelnames=("stage",),
        ),
        "commit": reg.histogram(
            "tx_commit_latency_seconds",
            "sampled per-tx end-to-end latency: check_tx ingress to "
            "block commit",
        ),
        "visible": reg.histogram(
            "tx_visible_latency_seconds",
            "sampled per-tx end-to-end latency: check_tx ingress to "
            "DeliverTx event delivery",
        ),
        "query": reg.histogram(
            "query_stage_seconds",
            "per-read span of an abci_query from the previous stamped "
            "stage to this one (sampled reads only)",
            labelnames=("stage",),
        ),
    }
    setattr(reg, _hist_attr, fams)
    return fams


class TxTrace:
    """One sampled tx's lifecycle record. Mutated only through the
    recorder; published (RPC readers, dumps) as to_json snapshots. The
    tx HASH (the cross-node causal id) is computed lazily — at seal or
    first read, never on the ingress path."""

    __slots__ = ("tx", "hash", "source", "stamps", "height", "outcome",
                 "completed_at", "gate_rid", "reap_block", "awaits_reply")

    def __init__(self, tx: bytes, source: str):
        self.tx = tx
        self.hash: bytes | None = None
        self.source = source
        self.stamps: dict[str, float] = {}
        self.height = 0
        self.outcome: str | None = None  # committed/rejected/evicted/...
        self.completed_at = 0.0
        self.gate_rid = ""
        self.reap_block: bytes | None = None
        self.awaits_reply = False

    def ensure_hash(self) -> bytes:
        h = self.hash
        if h is None:
            from tendermint_tpu.types.tx import tx_hash

            h = self.hash = tx_hash(self.tx)
        return h

    def spans(self, stamps: dict | None = None) -> dict[str, float]:
        """Span attributed to each stamped stage: seconds since the
        PREVIOUS stamped instant, in time order (ties in canonical
        order). Telescoping by construction — summing the spans through
        block_commit reproduces the commit latency exactly."""
        return _spans(self.stamps if stamps is None else stamps, _RANK)

    def to_json(self) -> dict:
        # snapshot FIRST: an RPC reader serializes in-flight traces
        # while stamping threads insert — dict(d) is one C-level copy
        # under the GIL, where iterating the live dict could raise
        # "changed size during iteration" mid-triage
        stamps = dict(self.stamps)
        ingress = stamps.get("rpc_ingress")
        commit = stamps.get("block_commit")
        visible = stamps.get("event_delivery")
        out = {
            "hash": self.ensure_hash().hex().upper(),
            "source": self.source,
            "height": self.height,
            "outcome": self.outcome,
            "stages": stamps,
            "spans": {k: round(v, 6)
                      for k, v in self.spans(stamps).items()},
            "commit_latency_s": (
                round(commit - ingress, 6)
                if ingress is not None and commit is not None else None
            ),
            "visible_latency_s": (
                round(visible - ingress, 6)
                if ingress is not None and visible is not None else None
            ),
            "completed_at": self.completed_at or None,
        }
        if self.gate_rid:
            out["gate_rid"] = self.gate_rid
        return out


# the sampled read this thread is serving (RPC handler threads only): the
# app's client and the app stamp it without a handle on the recorder
_serving = threading.local()


def stamp_read(stage: str) -> None:
    """Stamp `stage` (keep-first) on the sampled read this thread
    serves; a thread serving no sampled read pays one getattr."""
    tr = getattr(_serving, "read", None)
    if tr is not None and stage not in tr.stamps:
        tr.stamps[stage] = time.time()


class ReadTrace:
    """One sampled read's stamps, keyed by its JSON-RPC id."""

    __slots__ = ("id", "stamps")

    def __init__(self, rid, at: float):
        self.id = rid
        self.stamps: dict[str, float] = {"rpc_ingress": at}

    def to_json(self) -> dict:
        stamps = dict(self.stamps)
        return {"id": self.id, "stages": stamps,
                "spans": {k: round(v, 6)
                          for k, v in _spans(stamps, _READ_RANK).items()}}


class TxTraceRecorder:
    """Sampled per-tx lifecycle spans keyed by tx bytes in flight and
    by tx hash at rest (the ring). One recorder per node — the mempool,
    its reactor, the consensus state and the RPC handlers all stamp the
    same instance (node/node.py wires it; sites guard None for
    bare-harness tests)."""

    def __init__(self, ring: int | None = None, sample_n: int | None = None,
                 max_active: int | None = None):
        import os

        self._enabled = os.environ.get(
            "TENDERMINT_TXTRACE_DISABLE", "") != "1"
        self.sample_n = (
            sample_n if sample_n is not None
            else int(_env_number("TENDERMINT_TXTRACE_SAMPLE_N", 4, cast=int))
        )
        self.max_active = max(1, (
            max_active if max_active is not None
            else int(_env_number("TENDERMINT_TXTRACE_MAX_ACTIVE", 256,
                                 cast=int))
        ))
        if ring is None:
            ring = max(1, int(_env_number("TENDERMINT_TXTRACE_RING", 2048,
                                          cast=int)))
        self._ring: deque[TxTrace] = deque(maxlen=ring)
        self._read_ring: deque[ReadTrace] = deque(maxlen=4 * ring)
        self._reads = itertools.count()
        self._mtx = threading.Lock()
        # insertion-ordered (py3.7 dict): the oldest active is the
        # eviction victim when the bound is hit
        self._active: dict[bytes, TxTrace] = {}
        # sampled txs whose submitter waits in broadcast_tx_commit: their
        # traces seal at rpc_reply (expect_reply / reply)
        self._awaiting: set[bytes] = set()
        # holders of the effective N (the mempool keeps `_trace_n` so its
        # check_tx fast path reads its OWN attribute; bind() registers it
        # and set_enabled pushes changes there too)
        self._holders: list = []
        # flat stats (node/telemetry.py txtrace producer)
        self.sampled = 0
        self.completed = 0
        self.rejected = 0
        self.evicted = 0
        self.metrics_registry = None

    def _n(self) -> int:
        return self.sample_n if self._enabled else 0

    def set_enabled(self, on: bool) -> None:
        self._enabled = bool(on)
        for h in self._holders:
            h._trace_n = self._n()

    def bind(self, holder) -> None:
        """Register a holder of the effective N: `holder._trace_n`."""
        self._holders.append(holder)
        holder._trace_n = self._n()

    # -- sampling decision (check_tx entry) --------------------------------

    def samples(self, tx: bytes) -> bool:
        return in_sample(tx, self._n())

    def maybe_trace(self, tx: bytes, source: str = "rpc",
                    at: float | None = None) -> bool:
        """The ingress gate: the sample rule + ingress. mempool.check_tx
        runs the rule inline on its own `_trace_n`."""
        if self.samples(tx):
            return self.ingress(tx, source, at)
        return False

    def ingress(self, tx: bytes, source: str = "rpc",
                at: float | None = None) -> bool:
        """A sampled tx entered check_tx: open its trace (stamping
        rpc_ingress). The tx hash is computed later — never here."""
        if not self._enabled:
            return False
        victim = None
        with self._mtx:
            if tx in self._active:
                return True  # resubmission of a tx already in flight
            self.sampled += 1
            tr = TxTrace(tx, source)
            tr.stamps["rpc_ingress"] = at if at is not None else time.time()
            if self._awaiting:
                tr.awaits_reply = tx in self._awaiting
            if len(self._active) >= self.max_active:
                victim = self._active.pop(next(iter(self._active)))
                self.evicted += 1
            self._active[tx] = tr
        if victim is not None:
            # seal OUTSIDE the table lock (_seal appends to the ring
            # under the same mutex)
            self._seal(victim, "evicted")
        return True

    def expect_reply(self, tx: bytes) -> bool:
        """broadcast_tx_commit is about to submit `tx` and wait for its
        commit: if it is in the sample (the answer), its trace seals at
        reply()."""
        if not self.samples(tx):
            return False
        with self._mtx:
            self._awaiting.add(tx)
        return True

    # -- stamping (hot paths: one dict.get when anything is in flight) -----

    def stamp(self, tx: bytes, stage: str, at: float | None = None) -> None:
        """Stamp one stage for one tx (keep-first). Untraced txs pay one
        dict.get; with nothing in flight, one attribute read."""
        if not self._active:
            return
        tr = self._active.get(tx)
        if tr is not None and stage not in tr.stamps:
            tr.stamps[stage] = at if at is not None else time.time()

    def stamp_present(self, txs, stage: str, at: float | None = None) -> None:
        """Stamp `stage` (keep-first) for every traced tx present in `txs`
        (a gate batch, a block's tx list) — one dict.get per tx, only
        while traces are in flight."""
        active = self._active
        if not active:
            return
        at = at if at is not None else time.time()
        get = active.get
        for t in txs:
            tr = get(bytes(t))
            if tr is not None and stage not in tr.stamps:
                tr.stamps[stage] = at

    def stamp_gate_dispatch(self, txs, rid: str,
                            at: float | None = None) -> None:
        """The gate's batch went to the verifier at `at` as the daemon
        request `rid` ("" where the host answered it)."""
        active = self._active
        if not active:
            return
        at = at if at is not None else time.time()
        get = active.get
        for t in txs:
            tr = get(t)
            if tr is not None and "gate_dispatch" not in tr.stamps:
                tr.stamps["gate_dispatch"] = at
                tr.gate_rid = rid

    def stamp_reap(self, txs, block_hash: bytes,
                   at: float | None = None) -> None:
        """This node reaped `txs` into its proposal `block_hash`: keep-
        LAST (a later round's re-reap replaces it); commit() keeps it only
        where that block is the one committed."""
        active = self._active
        if not active:
            return
        at = at if at is not None else time.time()
        get = active.get
        for t in txs:
            tr = get(bytes(t))
            if tr is not None:
                tr.stamps["reap"] = at
                tr.reap_block = block_hash

    def reject(self, tx: bytes, reason: str = "rejected") -> None:
        """Seal a traced tx that left the lifecycle early (bad
        signature, app CheckTx reject)."""
        if not self._active:
            return
        with self._mtx:
            tr = self._active.pop(tx, None)
        if tr is not None:
            self._seal(tr, reason)
            self.rejected += 1

    # -- commit-side stamps (consensus state, RPC) -------------------------

    def commit(self, txs, height: int, block_hash: bytes | None = None,
               at: float | None = None) -> None:
        """block_commit for every traced tx in the finalized block; the
        record learns its height here, and drops a reap of a block other
        than `block_hash` (this node's proposal lost the round)."""
        if not self._active:
            return
        at = at if at is not None else time.time()
        get = self._active.get
        for t in txs:
            tr = get(bytes(t))
            if tr is not None:
                if "block_commit" not in tr.stamps:
                    tr.stamps["block_commit"] = at
                tr.height = height
                if tr.reap_block is not None and block_hash is not None \
                        and tr.reap_block != block_hash:
                    tr.stamps.pop("reap", None)
                tr.reap_block = None

    def delivered(self, txs, at: float | None = None) -> None:
        """event_delivery for every traced tx in the block, then seal —
        except a trace whose submitter waits for its reply (called after
        the event flush, serial and pipelined modes both)."""
        if not self._active:
            return
        at = at if at is not None else time.time()
        done = []
        with self._mtx:
            for t in txs:
                b = bytes(t)
                tr = self._active.get(b)
                if tr is None:
                    continue
                if "event_delivery" not in tr.stamps:
                    tr.stamps["event_delivery"] = at
                if not tr.awaits_reply:
                    del self._active[b]
                    done.append(tr)
        for tr in done:
            self._seal(tr, "committed")
            self.completed += 1

    def reply(self, tx: bytes, at: float | None = None) -> None:
        """broadcast_tx_commit returns for `tx`: stamp rpc_reply and seal
        the trace that waited for it (committed, or `unanswered` where
        the handler gave up first)."""
        with self._mtx:
            self._awaiting.discard(tx)
            tr = self._active.get(tx)
            if tr is None or not tr.awaits_reply:
                return
            del self._active[tx]
        tr.stamps.setdefault("rpc_reply", at if at is not None else time.time())
        committed = "block_commit" in tr.stamps
        self._seal(tr, "committed" if committed else "unanswered")
        if committed:
            self.completed += 1

    # -- reads (the RPC handler's thread) ----------------------------------

    def read_ingress(self, rid, at: float | None = None) -> ReadTrace | None:
        """An abci_query arrived with JSON-RPC id `rid`: open its trace if
        the node's count of reads samples it, as the calling thread's."""
        n = self._n()
        if n <= 0 or next(self._reads) % n:
            return None
        tr = _serving.read = ReadTrace(
            rid, at if at is not None else time.time())
        return tr

    def read_reply(self, tr: ReadTrace, at: float | None = None) -> None:
        """The read's reply is written: stamp rpc_reply, seal."""
        _serving.read = None
        tr.stamps.setdefault("rpc_reply", at if at is not None else time.time())
        try:
            hist = txtrace_hists(self.metrics_registry)["query"]
            for stage, span in _spans(tr.stamps, _READ_RANK).items():
                hist.labels(stage=stage).observe(span)
        except Exception:  # noqa: BLE001 — never break the reply path
            pass
        with self._mtx:
            self._read_ring.append(tr)

    def read_dump(self) -> list[dict]:
        """The sealed read traces, oldest first (the stop dump's
        `query_traces`)."""
        with self._mtx:
            items = list(self._read_ring)
        return [tr.to_json() for tr in items]

    # -- sealing + metrics -------------------------------------------------

    def _seal(self, tr: TxTrace, outcome: str) -> None:
        tr.outcome = outcome
        tr.completed_at = time.time()
        tr.ensure_hash()  # off the ingress path by design; pin it now
        tr.tx = None      # at rest a trace holds its hash, not the bytes
        self._observe(tr)
        with self._mtx:
            self._ring.append(tr)

    def _observe(self, tr: TxTrace) -> None:
        """Feed the sealed trace into the scrape-side distributions.
        Failure-proof like the consensus trace probes — attribution must
        never break the path that sealed the trace."""
        try:
            hists = txtrace_hists(self.metrics_registry)
            for stage, span in tr.spans().items():
                hists["stage"].labels(stage=stage).observe(span)
            ingress = tr.stamps.get("rpc_ingress")
            if ingress is None:
                return
            commit = tr.stamps.get("block_commit")
            if commit is not None:
                hists["commit"].observe(max(0.0, commit - ingress))
            visible = tr.stamps.get("event_delivery")
            if visible is not None:
                hists["visible"].observe(max(0.0, visible - ingress))
        except Exception:  # noqa: BLE001
            pass

    # -- reads (RPC threads, dumps) ----------------------------------------

    def active(self) -> list[dict]:
        """In-flight traces, oldest first — a partition-parked tx shows
        up HERE, stages frozen at wherever it stalled."""
        with self._mtx:
            return [tr.to_json() for tr in self._active.values()]

    def last(self, n: int = 20) -> list[dict]:
        """Newest-first slice of the completed ring (sliced BEFORE
        serialization — fleets poll this)."""
        n = max(1, int(n))
        with self._mtx:
            items = list(self._ring)
        return [tr.to_json() for tr in list(reversed(items))[:n]]

    def dump(self) -> list[dict]:
        """The whole completed ring, oldest first, then the traces still
        in flight: what the flight recorder's stop dump carries."""
        with self._mtx:
            items = list(self._ring) + list(self._active.values())
        return [tr.to_json() for tr in items]

    def stats(self) -> dict:
        """Flat gauges for the canonical map (txtrace_* families)."""
        with self._mtx:
            active = len(self._active)
        return {
            "sampled": self.sampled,
            "completed": self.completed,
            "rejected": self.rejected,
            "evicted": self.evicted,
            "active": active,
        }
