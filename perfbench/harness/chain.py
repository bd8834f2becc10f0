"""The served chain of a catch-up cell, made from the seed.

A chain of `n_blocks` blocks signed by `n_validators` distinct Ed25519
keys: every block carries the full LastCommit of its predecessor and a
small signedkv payload. It is written once, as a node's data directory
(block store + state DB, `sqlite`, the program's default backend), and the
serving nodes each get a copy of the state DB and a hard link to the one
block store file (a serving node only reads it). Not `filedb`: its
journal is rewritten whole on every append once it passes 64 MB, which
at 1.1 MB a block is terabytes for this chain (PERF.md, Open questions).

This is input data, the way weights are: it is built with the program's
own block and store types, because the served bytes have to be in the
program's format. What the chain MEANS — which key holds which value
after which height, whether a signature is valid — is recomputed by the
plain reference from the `ChainRecord` this module returns, never read
from the program.

Signing dominates (1000 signatures a block). Workers hold a slice of the
keys each and sign the block's one canonical message (the validator's
identity is not part of a vote's sign-bytes).
"""

from __future__ import annotations

import hashlib
import multiprocessing
import os
import shutil
import time
from dataclasses import dataclass, field


def derive(seed: int, *label) -> bytes:
    """32 bytes from the seed and a label: every key of a run."""
    h = hashlib.sha256(("perfbench/%d/" % seed).encode()
                       + "/".join(str(x) for x in label).encode())
    return h.digest()


def validator_lanes(seed: int, n_validators: int) -> list:
    """One valid lane per validator key of the seed's chain (the keys
    alone: a message of its own), for the daemon's warm-up."""
    from tendermint_tpu.crypto import ed25519 as ed

    make = make_signer()
    out = []
    for i in range(n_validators):
        secret = derive(seed, "val", i)
        msg = b"perfbench-warm-%d" % i
        out.append((ed.public_key(secret), msg, make(secret)(msg)))
    return out


def make_signer():
    """sign(seed32, msg) with key objects cached; OpenSSL when the
    machine has it, else the program's host signer."""
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
    except ImportError:
        from tendermint_tpu.crypto import ed25519 as ed

        return lambda secret: (lambda msg: ed.sign(secret, msg))
    return lambda secret: Ed25519PrivateKey.from_private_bytes(secret).sign


def _worker(conn, secrets: list[bytes]) -> None:
    make = make_signer()
    signers = [make(s) for s in secrets]
    while True:
        msg = conn.recv_bytes()
        if not msg:
            return
        conn.send_bytes(b"".join(s(msg) for s in signers))


class SignerPool:
    """n_workers processes, each signing for a contiguous slice of keys."""

    def __init__(self, secrets: list[bytes], n_workers: int):
        ctx = multiprocessing.get_context("spawn")
        n_workers = max(1, min(n_workers, len(secrets)))
        step = -(-len(secrets) // n_workers)
        self.procs, self.conns = [], []
        for i in range(0, len(secrets), step):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_worker, args=(child, secrets[i:i + step]),
                            daemon=True)
            p.start()
            child.close()
            self.procs.append(p)
            self.conns.append(parent)

    def sign_all(self, msg: bytes) -> list[bytes]:
        for c in self.conns:
            c.send_bytes(msg)
        out: list[bytes] = []
        for c in self.conns:
            blob = c.recv_bytes()
            out.extend(blob[i:i + 64] for i in range(0, len(blob), 64))
        return out

    def close(self) -> None:
        for c in self.conns:
            try:
                c.send_bytes(b"")
                c.close()
            except OSError:
                pass
        for p in self.procs:
            p.join(timeout=5)
            if p.is_alive():
                p.kill()
                p.join()


@dataclass
class ChainRecord:
    """What the reference needs to know about the chain, as made."""

    chain_id: str
    n_validators: int
    n_blocks: int
    data_dir: str = ""
    genesis_path: str = ""
    # per height (index h-1): the block's txs, its hash as served, the
    # app hash the header of h+1 carries, and the precommit lanes
    # (pubkey, sign-bytes, signature) of the commit FOR height h
    txs: list[list[bytes]] = field(default_factory=list)
    block_hash: list[str] = field(default_factory=list)
    app_hash_after: list[str] = field(default_factory=list)
    sign_bytes: list[bytes] = field(default_factory=list)
    signatures: list[list[bytes]] = field(default_factory=list)
    pubkeys: list[bytes] = field(default_factory=list)   # by validator index
    block_bytes: int = 0      # mean wire size of a block_response
    build_s: float = 0.0


def make_payload(seed: int, height: int, txs_per_block: int,
                 value_bytes: int, n_signers: int) -> list[bytes]:
    """The block's signedkv txs: pubkey || signature || key=value."""
    from tendermint_tpu.abci.apps.signedkv import make_sig_tx

    out = []
    for i in range(txs_per_block):
        signer = derive(seed, "tx-signer", (height * txs_per_block + i) % n_signers)
        key = b"c%d-%d-%d" % (seed % 1000003, height, i)
        val = (b"%d." % height) + hashlib.sha256(key).hexdigest().encode()
        out.append(make_sig_tx(signer, key + b"=" + val[:value_bytes]))
    return out


def build_chain(out_dir: str, *, seed: int, chain_id: str, n_validators: int,
                n_blocks: int, txs_per_block: int, value_bytes: int,
                n_tx_signers: int, n_workers: int) -> ChainRecord:
    """Write `<out_dir>/data/{blockstore,state}.sqlite` and
    `<out_dir>/genesis.json`; return the record of what was made."""
    import json
    import threading

    from tendermint_tpu.abci.apps.signedkv import SignedKVStoreApp
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.types import ABCIValidator
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.keys import PubKeyEd25519, SignatureEd25519
    from tendermint_tpu.libs.db import db_provider
    from tendermint_tpu.proxy.app_conn import AppConnConsensus
    from tendermint_tpu.state import execution as sm
    from tendermint_tpu.state.state import State
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, Vote
    from tendermint_tpu.types.block import Block, Commit, empty_commit
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.services import MockMempool
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT

    t0 = time.time()
    shutil.rmtree(out_dir, ignore_errors=True)
    data_dir = os.path.join(out_dir, "data")
    os.makedirs(data_dir)
    secrets = [derive(seed, "val", i) for i in range(n_validators)]
    pubs = [ed.public_key(s) for s in secrets]
    genesis = GenesisDoc(
        genesis_time_ns=1_700_000_000_000_000_000,
        chain_id=chain_id,
        validators=[GenesisValidator(PubKeyEd25519(p), 10, f"v{i}")
                    for i, p in enumerate(pubs)],
    )
    genesis.validate_and_complete()
    genesis_path = os.path.join(out_dir, "genesis.json")
    genesis.save_as(genesis_path)

    state_db = db_provider("state", "sqlite", data_dir)
    store_db = db_provider("blockstore", "sqlite", data_dir)
    state = State.get_state(state_db, genesis)
    store = BlockStore(store_db)
    app = SignedKVStoreApp()
    proxy = AppConnConsensus(LocalClient(app, threading.RLock()))
    app.init_chain([ABCIValidator(v.pub_key.to_json(), v.power)
                    for v in genesis.validators])
    vs = state.validators
    # the validator set orders by address: slot of each generated key
    slot_of = [vs.get_by_address(PubKeyEd25519(p).address())[0] for p in pubs]
    addr_at = [None] * n_validators
    pub_at = [b""] * n_validators
    for i, s in enumerate(slot_of):
        addr_at[s] = PubKeyEd25519(pubs[i]).address()
        pub_at[s] = pubs[i]
    # workers sign in SLOT order, so their output is index-aligned
    by_slot = [b""] * n_validators
    for i, s in enumerate(slot_of):
        by_slot[s] = secrets[i]
    pool = SignerPool(by_slot, n_workers)
    rec = ChainRecord(chain_id=chain_id, n_validators=n_validators,
                      n_blocks=n_blocks, data_dir=data_dir,
                      genesis_path=genesis_path, pubkeys=pub_at)
    part_size = state.params().block_gossip.block_part_size_bytes
    last_commit = empty_commit()
    wire = 0
    try:
        for h in range(1, n_blocks + 1):
            txs = make_payload(seed, h, txs_per_block, value_bytes, n_tx_signers)
            block, parts = Block.make_block(
                height=h, chain_id=chain_id, txs=txs, commit=last_commit,
                prev_block_id=state.last_block_id,
                val_hash=vs.hash(), app_hash=state.app_hash,
                part_size=part_size,
                time_ns=state.last_block_time_ns + 1_000_000_000,
            )
            block_id = BlockID(block.hash(), parts.header())
            proto = Vote(validator_address=addr_at[0], validator_index=0,
                         height=h, round_=0, type_=VOTE_TYPE_PRECOMMIT,
                         block_id=block_id)
            msg = proto.sign_bytes(chain_id)
            sigs = pool.sign_all(msg)
            commit = Commit(block_id, [
                Vote(validator_address=addr_at[i], validator_index=i,
                     height=h, round_=0, type_=VOTE_TYPE_PRECOMMIT,
                     block_id=block_id, signature=SignatureEd25519(sigs[i]))
                for i in range(n_validators)
            ])
            store.save_block(block, parts, commit)
            # apply_block without validate_block: the builder made the
            # commit it would re-verify (0.17 s of host verification a block)
            responses = sm.exec_block_on_proxy_app(None, proxy, block)
            state.set_block_and_validators(block.header, parts.header(), responses)
            sm.commit_state_update_mempool(state, proxy, block, MockMempool())
            rec.txs.append(txs)
            rec.block_hash.append(block.hash().hex().upper())
            rec.app_hash_after.append(state.app_hash.hex().upper())
            rec.sign_bytes.append(msg)
            rec.signatures.append(sigs)
            if h in (2, n_blocks):
                wire += len(json.dumps(
                    {"type": "block_response", "block": block.to_json()},
                    sort_keys=True))
            last_commit = commit
        state.save()
    finally:
        pool.close()
    state_db.close()
    store_db.close()
    for name in ("state", "blockstore"):
        _settle(os.path.join(data_dir, name + ".sqlite"))
    rec.block_bytes = wire // 2 if n_blocks >= 2 else wire
    rec.build_s = time.time() - t0
    return rec


def _settle(path: str) -> None:
    """Fold a closed database's write-ahead log into its one file, so
    that the file alone is the chain."""
    import sqlite3

    conn = sqlite3.connect(path)
    try:
        conn.execute("PRAGMA wal_checkpoint(TRUNCATE)")
    finally:
        conn.close()
    for suffix in ("-wal", "-shm"):
        if os.path.exists(path + suffix) and os.path.getsize(path + suffix) == 0:
            os.remove(path + suffix)


def install_copy(rec: ChainRecord, home: str) -> None:
    """Give a serving node's home the chain: its own state DB, a hard
    link to the shared block store (copy where links are not allowed)."""
    data = os.path.join(home, "data")
    os.makedirs(data, exist_ok=True)
    shutil.copyfile(os.path.join(rec.data_dir, "state.sqlite"),
                    os.path.join(data, "state.sqlite"))
    src = os.path.join(rec.data_dir, "blockstore.sqlite")
    dst = os.path.join(data, "blockstore.sqlite")
    try:
        os.link(src, dst)
    except OSError:
        shutil.copyfile(src, dst)
