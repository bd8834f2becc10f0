"""Big-committee vote plane bench (round 16): LIVE consensus at
100-400 validators, batched vs per-vote signature verification. Writes
BENCH_r16.json.

Three row families:

- consensus N=...    — a REAL ConsensusState (full receive routine, WAL,
                       block store) driven by an in-process committee:
                       N-1 stub validators whose proposals (when the
                       rotation elects them) and prevotes/precommits are
                       signed and injected through the peer queue — the
                       make_cs_and_stubs/Localnet scaffolding at
                       committee scale. Every height must collect +2/3
                       of N equal-power votes, so the receive routine
                       verifies ~2N gossiped signatures per height.
                       Each N runs twice: `batched` (the round-16
                       VoteBatcher — one verify_batch_async gateway call
                       per drained (height,round,type) group) vs
                       `per_vote` (vote_batching=False: the seed plane's
                       one-verify-per-vote receive path). The chains are
                       asserted BYTE-IDENTICAL per height (block hash,
                       part-set root, app hash) — batching changes WHEN
                       signatures verify, never what commits — and
                       batched blocks/s >= 1.3x per-vote is ASSERTED at
                       N=100 (the acceptance bar; measured ~2-3x on this
                       box, diluted by the pump's own pure-python vote
                       SIGNING which both modes pay identically).
- commit_verify N=...— verify_commit latency on an N-validator commit:
                       per-signature pure loop vs ONE gateway batch
                       (native AVX on the CPU floor, streamed devd when
                       a daemon serves — the live row joins the standard
                       live-chip queue).
- aggregate N=...    — the aggregate-commit format (types/agg_commit;
                       the round-22 cutover's wire object,
                       docs/upgrade.md): wire bytes of the full Commit
                       vs the half-aggregated object (asserted < 0.6x
                       at every N; ~0.22x at 400), a verification
                       round trip, and the round-22 verify-latency A/B
                       (`verify_s` gateway-batched vs
                       `verify_python_s` pure reference vs
                       `full_per_sig_s` — the per-sig loop the cutover
                       retires).

Chip-free by construction on this box; the consensus and commit-verify
batched rows ride whatever the gateway resolves (devd rows auto-join
when a daemon serves). Run from the repo root:
python benches/bench_committee.py  (BENCH_COMMITTEE_SMOKE=1 for the
~30 s tier-1 gate: N=100 consensus A/B + the 4/100 object rows).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SMOKE = os.environ.get("BENCH_COMMITTEE_SMOKE", "") == "1"
CONSENSUS_VALS = (
    [100] if SMOKE
    else [int(x) for x in os.environ.get(
        "BENCH_COMMITTEE_VALS", "4,32,100,400").split(",")]
)
OBJECT_VALS = [4, 100] if SMOKE else [4, 32, 100, 400]
N_HEIGHTS = int(os.environ.get("BENCH_COMMITTEE_HEIGHTS", "3"))
MIN_RATIO = float(os.environ.get("BENCH_COMMITTEE_MIN_RATIO", "1.3"))
ASSERT_AT = int(os.environ.get("BENCH_COMMITTEE_ASSERT_VALS", "100"))
GENESIS_NS = 1_700_000_000_000_000_000
CHAIN_ID = "bench_committee"


def _committee(n):
    """n seeded validators, sorted in validator-set (address) order —
    identical across runs so chains can be asserted byte-identical."""
    from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidatorFS

    pvs = []
    for i in range(n):
        seed = (b"committee-%05d" % i).ljust(32, b"\x00")
        pvs.append(PrivValidatorFS(gen_priv_key_ed25519(seed), None))
    pvs.sort(key=lambda pv: pv.get_address())
    doc = GenesisDoc(
        genesis_time_ns=GENESIS_NS,
        chain_id=CHAIN_ID,
        validators=[
            GenesisValidator(pv.get_pub_key(), 1, f"v{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    return doc, pvs


def _build_cs(doc, pvs):
    """A real ConsensusState over MemDB, operated by the height-1
    proposer's key; liveness timeouts generous (the pump is prompt, and
    a stray round bump would fork the byte-identity assert)."""
    import tempfile

    from tendermint_tpu.abci.apps.kvstore import KVStoreApp
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.config import test_config
    from tendermint_tpu.consensus.state import ConsensusState
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.libs.events import EventSwitch
    from tendermint_tpu.mempool import Mempool
    from tendermint_tpu.proxy.app_conn import AppConnConsensus, AppConnMempool
    from tendermint_tpu.state.state import State

    state = State.get_state(MemDB(), doc)
    proposer = state.validators.get_proposer()
    own_pv = next(pv for pv in pvs if pv.get_address() == proposer.address)
    app = KVStoreApp()
    mtx = threading.RLock()
    mp = Mempool(test_config().mempool, AppConnMempool(LocalClient(app, mtx)))
    cfg = test_config().consensus
    cfg.root_dir = tempfile.mkdtemp(prefix="bench-committee-")
    cfg.timeout_commit = 0.05
    cfg.skip_timeout_commit = True
    cfg.timeout_propose = 60.0
    cfg.timeout_prevote = 60.0
    cfg.timeout_precommit = 60.0
    evsw = EventSwitch()
    evsw.start()
    cs = ConsensusState(
        cfg, state, AppConnConsensus(LocalClient(app, mtx)),
        BlockStore(MemDB()), mp,
    )
    cs.set_event_switch(evsw)
    cs.set_priv_validator(own_pv)
    # the A/B isolates the VOTE plane: the deferred-apply pipeline is off
    # in both modes (empty blocks apply in microseconds), and block times
    # are pinned so chains are reproducible byte-for-byte
    cs.pipeline_apply = False
    cs.propose_time_source = lambda h: GENESIS_NS + h * 1_000_000_000
    return cs, own_pv


def _wait(cond, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.002)
    raise SystemExit(f"committee pump stalled waiting for {what}")


def _pump(cs, pvs, own_pv, heights):
    """The committee: for every height, propose (when the rotation
    elects a stub), then inject every stub's prevote and precommit —
    the full +2/3 formation path a real 100-400 node net exercises,
    minus the sockets."""
    from tendermint_tpu.consensus import messages as msgs
    from tendermint_tpu.consensus.round_state import RoundStep
    from tendermint_tpu.types import BlockID, Proposal, Vote
    from tendermint_tpu.types.block import Block, empty_commit
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, VOTE_TYPE_PREVOTE

    by_addr = {pv.get_address(): pv for pv in pvs}
    own_addr = own_pv.get_address()
    for h in range(1, heights + 1):
        # last_commit.has_all(): every straggler precommit of h-1 must be
        # absorbed before ANY height-h proposal reads make_commit() — the
        # byte-identity contract (a partial commit snapshot is exactly
        # the timing artifact the A/B must not measure)
        _wait(
            lambda: cs.rs.height == h
            and cs.state.last_block_height == h - 1
            and (h == 1 or (cs.rs.last_commit is not None
                            and cs.rs.last_commit.has_all())),
            60, f"height {h}",
        )
        proposer = cs.rs.validators.get_proposer()
        if proposer.address != own_addr:
            # the elected stub proposes: build the exact block the real
            # node would (pinned time, empty txs, the full last commit)
            commit = (
                empty_commit() if h == 1 else cs.rs.last_commit.make_commit()
            )
            block, parts = Block.make_block(
                height=h,
                chain_id=CHAIN_ID,
                txs=[],
                commit=commit,
                prev_block_id=cs.state.last_block_id,
                val_hash=cs.state.validators.hash(),
                app_hash=cs.state.app_hash,
                part_size=cs.state.params().block_gossip.block_part_size_bytes,
                time_ns=GENESIS_NS + h * 1_000_000_000,
            )
            proposal = by_addr[proposer.address].sign_proposal(
                CHAIN_ID, Proposal(h, 0, parts.header())
            )
            cs.set_proposal_msg(proposal, peer_id="pump")
            for i in range(parts.total):
                cs.add_peer_message(
                    msgs.BlockPartMessage(h, 0, parts.get_part(i)), "pump"
                )
        _wait(
            lambda: cs.rs.height == h and cs.rs.proposal_block is not None,
            60, f"proposal at {h}",
        )
        bid = BlockID(
            cs.rs.proposal_block.hash(), cs.rs.proposal_block_parts.header()
        )
        for type_ in (VOTE_TYPE_PREVOTE, VOTE_TYPE_PRECOMMIT):
            votes = []
            for i, pv in enumerate(pvs):
                if pv.get_address() == own_addr:
                    continue  # cs signs its own
                v = Vote(
                    validator_address=pv.get_address(),
                    validator_index=i,
                    height=h,
                    round_=0,
                    type_=type_,
                    block_id=bid,
                )
                votes.append(pv.sign_vote(CHAIN_ID, v))
            for v in votes:
                cs.add_vote_msg(v, peer_id="pump")
            if type_ == VOTE_TYPE_PREVOTE:
                # cs must lock + precommit before the precommit wave so
                # every height commits at round 0 in both modes
                _wait(
                    lambda: cs.rs.height > h
                    or (cs.rs.step >= RoundStep.PRECOMMIT),
                    60, f"precommit step at {h}",
                )
    _wait(lambda: cs.rs.height > heights, 60, "final commit")


def _run_consensus(n, batched):
    doc, pvs = _committee(n)
    cs, own_pv = _build_cs(doc, pvs)
    cs.vote_batching = batched
    pump_exc = []

    def pump():
        try:
            _pump(cs, pvs, own_pv, N_HEIGHTS)
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            pump_exc.append(exc)

    t = threading.Thread(target=pump, daemon=True)
    t0 = time.perf_counter()
    cs.start()
    t.start()
    t.join(timeout=120 + 10 * N_HEIGHTS)
    wall_s = time.perf_counter() - t0
    alive = t.is_alive()
    cs.stop()
    if pump_exc:
        raise SystemExit(f"committee pump failed: {pump_exc[0]}")
    if alive:
        raise SystemExit(f"committee run (n={n}) never finished")
    fps = {}
    for h in range(1, N_HEIGHTS + 1):
        meta = cs.block_store.load_block_meta(h)
        block = cs.block_store.load_block(h)
        fps[h] = (
            meta.block_id.hash.hex(),
            meta.block_id.parts_header.hash.hex(),
            block.header.app_hash.hex(),
        )
    row = {
        "row": f"consensus_n{n}_{'batched' if batched else 'per_vote'}",
        "validators": n,
        "heights": N_HEIGHTS,
        "wall_s": round(wall_s, 3),
        "blocks_per_sec": round(N_HEIGHTS / wall_s, 3),
        "vote_batches": cs.vote_batcher.batches,
        "vote_batched_sigs": cs.vote_batcher.batched_sigs,
        "vote_singletons": cs.vote_batcher.singletons,
        "platform": "host",
    }
    return row, fps


def _signed_commit(n, height=7):
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote

    doc, pvs = _committee(n)
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.state.state import State

    vals = State.get_state(MemDB(), doc).validators
    bid = BlockID(b"\x17" * 20, PartSetHeader(1, b"\x29" * 20))
    pres = []
    for i, pv in enumerate(pvs):
        v = Vote(pv.get_address(), i, height, 0, VOTE_TYPE_PRECOMMIT, bid)
        pres.append(pv.sign_vote(CHAIN_ID, v))
    return vals, bid, Commit(bid, pres), height


def _commit_verify_rows():
    from tendermint_tpu.ops import gateway

    verifier = gateway.Verifier(min_tpu_batch=4)
    platform = "devd" if verifier._kernel == "devd" else "host"
    rows = []
    for n in OBJECT_VALS:
        vals, bid, commit, height = _signed_commit(n)
        t0 = time.perf_counter()
        vals.verify_commit(CHAIN_ID, bid, height, commit)  # per-sig pure loop
        per_sig_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        vals.verify_commit(
            CHAIN_ID, bid, height, commit,
            batch_verifier=verifier.commit_batch_verifier(),
        )
        batched_s = time.perf_counter() - t0
        rows.append({
            "row": f"commit_verify_n{n}",
            "validators": n,
            "per_sig_s": round(per_sig_s, 4),
            "batched_s": round(batched_s, 4),
            "vs_per_sig": round(per_sig_s / batched_s, 2) if batched_s else 0.0,
            "platform": platform,
        })
    return rows


def _aggregate_rows():
    from tendermint_tpu.crypto import ed25519_agg
    from tendermint_tpu.types.agg_commit import AggregateCommit

    rows = []
    for n in OBJECT_VALS:
        vals, bid, commit, height = _signed_commit(n)
        t0 = time.perf_counter()
        agg = AggregateCommit.from_commit(commit, CHAIN_ID, vals)
        agg_build_s = time.perf_counter() - t0
        # round 22: the verify-latency A/B the cutover rides — the same
        # aggregate through the gateway-batched dual-scalar-mul path
        # (devd/sharded/direct kernel, CPU floor included) vs the
        # pure-python reference, next to the full commit's per-sig loop
        t0 = time.perf_counter()
        agg.verify(CHAIN_ID, vals)  # gateway-batched (default verifier)
        agg_verify_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        agg.verify(CHAIN_ID, vals,
                   agg_verifier=ed25519_agg.verify_aggregate)
        agg_verify_py_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        vals.verify_commit(CHAIN_ID, bid, height, commit)
        full_per_sig_s = time.perf_counter() - t0
        commit_bytes = len(commit.to_bytes())
        agg_bytes = len(agg.to_bytes())
        ratio = agg_bytes / commit_bytes
        assert ratio < 0.6, (
            f"aggregate commit only {ratio:.2f}x full at n={n} "
            "(expected < 0.6x)"
        )
        # wire round trip must still verify
        AggregateCommit.from_bytes(agg.to_bytes()).verify(CHAIN_ID, vals)
        rows.append({
            "row": f"aggregate_n{n}",
            "validators": n,
            "commit_bytes": commit_bytes,
            "aggregate_bytes": agg_bytes,
            "bytes_vs_full": round(ratio, 3),
            "aggregate_s": round(agg_build_s, 4),
            "verify_s": round(agg_verify_s, 4),
            "verify_python_s": round(agg_verify_py_s, 4),
            "full_per_sig_s": round(full_per_sig_s, 4),
            "verify_vs_per_sig": round(full_per_sig_s / agg_verify_s, 2)
            if agg_verify_s else 0.0,
            "platform": "host",
        })
    return rows


def main() -> None:
    os.environ.setdefault("TENDERMINT_TPU_PLATFORM", "cpu")
    rows = []
    ratios = {}
    for n in CONSENSUS_VALS:
        per_row, per_fps = _run_consensus(n, batched=False)
        bat_row, bat_fps = _run_consensus(n, batched=True)
        assert bat_fps == per_fps, (
            f"batched chain diverged from per-vote at n={n}: "
            f"{bat_fps} vs {per_fps}"
        )
        assert bat_row["vote_batches"] >= 1, "batched run never batched"
        assert per_row["vote_batches"] == 0, "per-vote run dispatched a batch"
        ratio = bat_row["blocks_per_sec"] / per_row["blocks_per_sec"]
        ratios[n] = ratio
        rows.extend([per_row, bat_row, {
            "row": f"consensus_n{n}_batched_vs_per_vote",
            "validators": n,
            "ratio": round(ratio, 3),
            "byte_identity": "block hash + part-set root + app hash, "
                             "all heights, both modes",
        }])
        print(f"  n={n}: per-vote {per_row['blocks_per_sec']} blk/s, "
              f"batched {bat_row['blocks_per_sec']} blk/s ({ratio:.2f}x)",
              file=sys.stderr)
    if ASSERT_AT in ratios:
        assert ratios[ASSERT_AT] >= MIN_RATIO, (
            f"batched vote verify only {ratios[ASSERT_AT]:.2f}x per-vote at "
            f"{ASSERT_AT} validators (floor {MIN_RATIO}x)"
        )
    rows.extend(_commit_verify_rows())
    rows.extend(_aggregate_rows())

    out = {
        "bench": "committee",
        "smoke": SMOKE,
        "heights": N_HEIGHTS,
        "min_ratio_asserted": MIN_RATIO,
        "assert_at_validators": ASSERT_AT,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    if not SMOKE:
        with open(os.path.join(ROOT, "BENCH_r16.json"), "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    summary = {
        "config": "16_committee",
        "ratio_at_assert": round(ratios.get(ASSERT_AT, 0.0), 3),
        "agg_bytes_vs_full": next(
            (r["bytes_vs_full"] for r in rows
             if r["row"] == f"aggregate_n{OBJECT_VALS[-1]}"), None
        ),
        "detail": {"rows": len(rows), "smoke": SMOKE},
    }
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
