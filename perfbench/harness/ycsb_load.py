"""YCSB's load phase for a `signedkv` chain: the operator's bulk load of
every record before the users arrive, as blocks of a chain.

`build_loaded_chain` makes, from the seed, a chain whose blocks carry
one insert a record, each signed with the ONE loader key (the operator's:
a chain's genesis accounts are not signed in by their owners either, and
`signedkv` lets any key write any record), committed by the run's own
validators. `install` gives a validator's home a copy of it; the node
then replays every block into its app at boot (the handshake's replay),
verifying every insert's signature the way it verifies a live block's.
Nothing is written into an app's memory.

Built beside `harness/chain.py` (the catch-up cell's served chain) with
the program's own block, store and state types, because the stored bytes
have to be in the program's format. What the records hold is recomputed
by `reference/ycsb_ref.py`, never read from here.
"""

from __future__ import annotations

import multiprocessing
import os
import shutil
import threading
import time

from harness import ycsb
from harness.chain import _settle

GENESIS_TIME_NS = 1_700_000_000_000_000_000


def _sign_slice(args) -> list[bytes]:
    """A worker: the signed inserts of records lo..hi-1."""
    seed, lo, hi = args
    pub, sign = ycsb.make_keypair()(ycsb.loader_secret(seed))
    out = []
    for r in range(lo, hi):
        payload = ycsb.record_key(r) + b"=" + ycsb.record_value(seed, r, 0)
        out.append(pub + sign(payload) + payload)
    return out


def signed_inserts(seed: int, recordcount: int, n_workers: int) -> list[bytes]:
    step = -(-recordcount // max(1, n_workers * 4))
    jobs = [(seed, lo, min(recordcount, lo + step))
            for lo in range(0, recordcount, step)]
    if n_workers <= 1:
        parts = [_sign_slice(j) for j in jobs]
    else:
        ctx = multiprocessing.get_context("spawn")
        with ctx.Pool(n_workers) as pool:
            parts = pool.map(_sign_slice, jobs)
    return [tx for part in parts for tx in part]


class _Trusting:
    """The builder made these signatures a moment ago; the nodes check
    every one of them when they replay the chain."""

    @staticmethod
    def verify_batch(items):
        return [True] * len(items)


def build_loaded_chain(out_dir: str, *, seed: int, genesis, pvs, recordcount: int,
                       txs_per_block: int, n_workers: int) -> dict:
    """Write `<out_dir>/data/{blockstore,state}.sqlite`; return what was
    made: blocks, seconds, the loader's public key."""
    from tendermint_tpu.abci.apps.signedkv import SignedKVStoreApp
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.types import ABCIValidator
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.libs.db import db_provider
    from tendermint_tpu.proxy.app_conn import AppConnConsensus
    from tendermint_tpu.state import execution as sm
    from tendermint_tpu.state.state import State
    from tendermint_tpu.types import Vote
    from tendermint_tpu.types.block import Block, Commit, empty_commit
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.services import MockMempool
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT

    t0 = time.time()
    shutil.rmtree(out_dir, ignore_errors=True)
    data_dir = os.path.join(out_dir, "data")
    os.makedirs(data_dir)
    txs_all = signed_inserts(seed, recordcount, n_workers)
    signed_s = time.time() - t0
    state_db = db_provider("state", "sqlite", data_dir)
    store_db = db_provider("blockstore", "sqlite", data_dir)
    state = State.get_state(state_db, genesis)
    store = BlockStore(store_db)
    app = SignedKVStoreApp()
    app.deliver_verifier = _Trusting()
    proxy = AppConnConsensus(LocalClient(app, threading.RLock()))
    app.init_chain([ABCIValidator(v.pub_key.to_json(), v.power)
                    for v in genesis.validators])
    vs = state.validators
    by_slot = sorted(pvs, key=lambda pv: vs.get_by_address(pv.get_address())[0])
    part_size = state.params().block_gossip.block_part_size_bytes
    last_commit = empty_commit()
    n_blocks = -(-recordcount // txs_per_block)
    for h in range(1, n_blocks + 1):
        txs = txs_all[(h - 1) * txs_per_block:h * txs_per_block]
        if len(txs) == 1:
            raise ValueError("a block of one insert would take the app's "
                             "per-transaction path: change txs_per_block")
        block, parts = Block.make_block(
            height=h, chain_id=genesis.chain_id, txs=txs, commit=last_commit,
            prev_block_id=state.last_block_id, val_hash=vs.hash(),
            app_hash=state.app_hash, part_size=part_size,
            time_ns=GENESIS_TIME_NS + h * 1_000_000_000)
        block_id = BlockID(block.hash(), parts.header())
        votes = []
        for i, pv in enumerate(by_slot):
            vote = Vote(validator_address=pv.get_address(), validator_index=i,
                        height=h, round_=0, type_=VOTE_TYPE_PRECOMMIT,
                        block_id=block_id)
            votes.append(vote.with_signature(
                pv.priv_key.sign(vote.sign_bytes(genesis.chain_id))))
        commit = Commit(block_id, votes)
        store.save_block(block, parts, commit)
        responses = sm.exec_block_on_proxy_app(None, proxy, block)
        state.set_block_and_validators(block.header, parts.header(), responses)
        sm.commit_state_update_mempool(state, proxy, block, MockMempool())
        last_commit = commit
    state.save()
    state_db.close()
    store_db.close()
    for name in ("state", "blockstore"):
        _settle(os.path.join(data_dir, name + ".sqlite"))
    return {"data_dir": data_dir, "blocks": n_blocks,
            "records": recordcount, "signing_s": round(signed_s, 2),
            "build_s": round(time.time() - t0, 2),
            "store_bytes": os.path.getsize(
                os.path.join(data_dir, "blockstore.sqlite"))}


def install(made: dict, home: str) -> None:
    """A validator's own copies: it will append to both."""
    data = os.path.join(home, "data")
    os.makedirs(data, exist_ok=True)
    for name in ("state.sqlite", "blockstore.sqlite"):
        shutil.copyfile(os.path.join(made["data_dir"], name),
                        os.path.join(data, name))
