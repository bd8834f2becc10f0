"""ABCI apps/clients/proxy and state execution pipeline tests."""

import json
import threading

import pytest

from tendermint_tpu.abci.apps import CounterApp, KVStoreApp, NilApp, PersistentKVStoreApp
from tendermint_tpu.abci.client import ABCIServer, LocalClient, SocketClient
from tendermint_tpu.abci.types import ABCIValidator, Header as ABCIHeader
from tendermint_tpu.crypto.keys import TYPE_ED25519, gen_priv_key_ed25519
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.events import EventCache, EventSwitch
from tendermint_tpu.proxy import AppConns, LocalClientCreator, default_client_creator
from tendermint_tpu.state import State, apply_block, exec_commit_block, validate_block
from tendermint_tpu.state.execution import InvalidBlockError, update_validators
from tendermint_tpu.state.txindex import KVTxIndexer
from tendermint_tpu.types import (
    Block,
    BlockID,
    GenesisDoc,
    GenesisValidator,
    VoteSet,
    VOTE_TYPE_PRECOMMIT,
)
from tendermint_tpu.types.block import empty_commit
from tendermint_tpu.types.priv_validator import PrivValidatorFS
from tendermint_tpu.types.services import MockMempool

from tests.test_types import make_val_set, signed_vote


class TestKVStoreApp:
    def test_deliver_query_commit(self):
        app = KVStoreApp()
        assert app.deliver_tx(b"name=satoshi").is_ok
        res = app.commit()
        assert res.is_ok and len(res.data) == 20
        q = app.query(b"name")
        assert q.value == b"satoshi"
        assert app.query(b"missing").value == b""
        # app hash deterministic across instances
        app2 = KVStoreApp()
        app2.deliver_tx(b"name=satoshi")
        assert app2.commit().data == res.data

    def test_info_tracks_height(self):
        app = KVStoreApp()
        assert app.info().last_block_height == 0
        app.deliver_tx(b"a=1")
        app.commit()
        info = app.info()
        assert info.last_block_height == 1
        assert info.last_block_app_hash == app.app_hash


class TestPersistentKVStore:
    def test_persistence(self, tmp_path):
        app = PersistentKVStoreApp(str(tmp_path))
        app.deliver_tx(b"k=v")
        h = app.commit()
        app2 = PersistentKVStoreApp(str(tmp_path))
        assert app2.height == 1
        assert app2.app_hash == h.data
        assert app2.query(b"k").value == b"v"

    def test_val_tx_diffs(self, tmp_path):
        app = PersistentKVStoreApp(str(tmp_path))
        pub = gen_priv_key_ed25519(b"val-seed").pub_key()
        app.begin_block(b"", ABCIHeader())
        assert app.deliver_tx(b"val:" + pub.raw.hex().encode() + b"/10").is_ok
        diffs = app.end_block(1).diffs
        assert len(diffs) == 1 and diffs[0].power == 10
        assert not app.deliver_tx(b"val:nothex/10").is_ok


class TestCounterApp:
    def test_serial_ordering(self):
        app = CounterApp(serial=True)
        assert app.deliver_tx(b"\x00").is_ok
        assert app.deliver_tx(b"\x01").is_ok
        assert not app.deliver_tx(b"\x05").is_ok  # gap
        assert app.check_tx(b"\x02").is_ok
        assert not app.check_tx(b"\x00").is_ok  # below check count

    def test_commit_hash(self):
        app = CounterApp()
        assert app.commit().data == b""
        app.deliver_tx(b"\x00")
        assert app.commit().data.endswith(b"\x01")


class TestSocketClient:
    def test_roundtrip_over_tcp(self, tmp_path):
        app = KVStoreApp()
        server = ABCIServer(app, "127.0.0.1:0")
        server.start()
        try:
            cli = SocketClient(server.addr)
            cli.start()
            assert cli.echo_sync("hello") == "hello"
            assert cli.info_sync().last_block_height == 0
            assert cli.deliver_tx_sync(b"x=42").is_ok
            res = cli.commit_sync()
            assert res.is_ok and len(res.data) == 20
            assert cli.query_sync(b"x").value == b"42"
            # async pipeline
            rrs = [cli.deliver_tx_async(b"k%d=%d" % (i, i)) for i in range(10)]
            for rr in rrs:
                assert rr.wait(5).is_ok
            cli.stop()
        finally:
            server.stop()


class TestAppConns:
    def test_three_connections(self):
        creator = LocalClientCreator(CounterApp(serial=True))
        conns = AppConns(creator)
        conns.start()
        assert conns.query().info_sync() is not None
        assert conns.mempool().check_tx_async(b"\x00").wait(1).is_ok
        conns.consensus().begin_block_sync(b"", ABCIHeader())
        assert conns.consensus().deliver_tx_async(b"\x00").wait(1).is_ok
        assert conns.consensus().commit_sync().is_ok

    def test_default_creator_names(self, tmp_path):
        for name in ("kvstore", "dummy", "counter", "nilapp"):
            c = default_client_creator(name, str(tmp_path))
            assert isinstance(c, LocalClientCreator)


def make_genesis(n=4, power=10, chain_id="exec-chain"):
    vs, privs = make_val_set(n, power)
    doc = GenesisDoc(
        genesis_time_ns=0,
        chain_id=chain_id,
        validators=[
            GenesisValidator(v.pub_key, v.voting_power) for v in vs.validators
        ],
    )
    return doc, vs, privs


def make_next_block(state: State, txs, privs, part_size=4096):
    """Build a valid next block with a proper commit for the last block."""
    height = state.last_block_height + 1
    if height == 1:
        commit = empty_commit()
    else:
        voteset = VoteSet(
            state.chain_id, height - 1, 0, VOTE_TYPE_PRECOMMIT, state.last_validators
        )
        for p in privs:
            voteset.add_vote(
                signed_vote(
                    p, state.last_validators, height - 1, 0, VOTE_TYPE_PRECOMMIT,
                    state.last_block_id, chain_id=state.chain_id,
                )
            )
        commit = voteset.make_commit()
    block, ps = Block.make_block(
        height, state.chain_id, txs, commit,
        state.last_block_id, state.validators.hash(), state.app_hash, part_size,
        time_ns=height * 10**9,
    )
    return block, ps


class TestStatePersistence:
    def test_genesis_and_reload(self):
        doc, vs, _ = make_genesis()
        db = MemDB()
        s = State.get_state(db, doc)
        assert s.last_block_height == 0
        assert s.validators.hash() == vs.hash()
        s2 = State.get_state(db, doc)
        assert s2.equals(s)

    def test_validators_history(self):
        doc, vs, privs = make_genesis()
        db = MemDB()
        s = State.get_state(db, doc)
        # heights 1..3 without changes: pointer chain resolves to genesis set
        app = KVStoreApp()
        conns = AppConns(LocalClientCreator(app))
        conns.start()
        for h in range(1, 4):
            block, ps = make_next_block(s, [b"tx%d" % h], privs)
            apply_block(s, None, conns.consensus(), block, ps.header(), MockMempool())
        for h in range(1, 4):
            assert s.load_validators(h).hash() == vs.hash()


class TestExecution:
    def _setup(self, app=None):
        doc, vs, privs = make_genesis()
        db = MemDB()
        s = State.get_state(db, doc)
        s.tx_indexer = KVTxIndexer(MemDB())
        conns = AppConns(LocalClientCreator(app or KVStoreApp()))
        conns.start()
        return s, conns, privs

    def test_apply_blocks_advances_state(self):
        s, conns, privs = self._setup()
        for h in range(1, 4):
            block, ps = make_next_block(s, [b"key%d=val%d" % (h, h)], privs)
            apply_block(s, None, conns.consensus(), block, ps.header(), MockMempool())
            assert s.last_block_height == h
            assert s.last_block_id.hash == block.hash()
        # app hash binds app state
        q = conns.query().query_sync(b"key1")
        assert q.value == b"val1"
        # tx indexed
        from tendermint_tpu.types.tx import tx_hash

        r = s.tx_indexer.get(tx_hash(b"key1=val1"))
        assert r is not None and r.height == 1

    def test_validate_block_rejects(self):
        s, conns, privs = self._setup()
        block, ps = make_next_block(s, [b"a=1"], privs)
        apply_block(s, None, conns.consensus(), block, ps.header(), MockMempool())
        # wrong height
        bad, _ = make_next_block(s, [b"b=2"], privs)
        bad.header.height = 99
        with pytest.raises(InvalidBlockError):
            validate_block(s, bad)
        # tampered commit (drop one sig -> below quorum)
        bad2, _ = make_next_block(s, [b"b=2"], privs)
        signed = [i for i, p in enumerate(bad2.last_commit.precommits) if p]
        for i in signed[:2]:
            bad2.last_commit.precommits[i] = None
        bad2.header.last_commit_hash = bad2.last_commit.hash()
        bad2.header.data_hash = b""
        bad2.fill_header()
        with pytest.raises(InvalidBlockError):
            validate_block(s, bad2)

    def test_events_fired_on_flush(self):
        s, conns, privs = self._setup()
        evsw = EventSwitch()
        got = []
        from tendermint_tpu.types.events import event_string_tx
        from tendermint_tpu.types.tx import tx_hash

        tx = b"watched=1"
        evsw.add_listener_for_event("t", event_string_tx(tx_hash(tx)), got.append)
        cache = EventCache(evsw)
        block, ps = make_next_block(s, [tx], privs)
        apply_block(s, cache, conns.consensus(), block, ps.header(), MockMempool())
        assert got == []  # not yet flushed
        cache.flush()
        assert len(got) == 1 and got[0].height == 1

    def test_valset_change_via_endblock(self, tmp_path):
        app = PersistentKVStoreApp(str(tmp_path))
        s, conns, privs = self._setup(app)
        new_pub = gen_priv_key_ed25519(b"newval").pub_key()
        val_tx = b"val:" + new_pub.raw.hex().encode() + b"/7"
        block, ps = make_next_block(s, [val_tx], privs)
        apply_block(s, None, conns.consensus(), block, ps.header(), MockMempool())
        assert s.validators.size() == 5
        assert s.last_height_validators_changed == 2
        _, v = s.validators.get_by_address(new_pub.address())
        assert v is not None and v.voting_power == 7
        # removal
        rm_tx = b"val:" + new_pub.raw.hex().encode() + b"/0"
        block2, ps2 = make_next_block(s, [rm_tx], privs)
        apply_block(s, None, conns.consensus(), block2, ps2.header(), MockMempool())
        assert s.validators.size() == 4

    def test_exec_commit_block(self):
        s, conns, privs = self._setup()
        block, ps = make_next_block(s, [b"z=9"], privs)
        app_hash = exec_commit_block(conns.consensus(), block)
        assert len(app_hash) == 20

    def test_per_tx_dispatch_matches_grouped_dispatch(self, tmp_path):
        """A connection without `deliver_txs_async` gets one DeliverTx a
        tx; the block's results and the app hash are those of the
        grouped dispatch, a rejected tx included."""
        from tendermint_tpu.state.execution import exec_block_on_proxy_app

        class PerTxOnly:
            """The consensus connection less its grouped call."""

            def __init__(self, conn):
                self._conn = conn
                self.delivered = 0

            def deliver_tx_async(self, tx):
                self.delivered += 1
                return self._conn.deliver_tx_async(tx)

            def __getattr__(self, name):
                if name == "deliver_txs_async":
                    raise AttributeError(name)
                return getattr(self._conn, name)

        txs = [b"a=1", b"b=2", b"val:zz/1", b"a=3"]
        outcomes = []
        for per_tx in (False, True):
            s, conns, privs = self._setup(
                PersistentKVStoreApp(str(tmp_path / str(per_tx))))
            conn = conns.consensus()
            if per_tx:
                conn = PerTxOnly(conn)
            block, _ = make_next_block(s, txs, privs)
            res = exec_block_on_proxy_app(None, conn, block)
            if per_tx:
                assert conn.delivered == len(txs)
            outcomes.append((
                [(r.code, r.data, r.log) for r in res.deliver_tx],
                conns.consensus().commit_sync().data,
            ))
        assert outcomes[0] == outcomes[1]
        assert [c == 0 for c, _, _ in outcomes[0][0]] == [True, True, False, True]

    def test_update_validators_errors(self):
        _, vs, _ = make_genesis()
        missing = gen_priv_key_ed25519(b"missing").pub_key()
        with pytest.raises(ValueError):
            update_validators(
                vs, [ABCIValidator([TYPE_ED25519, missing.raw.hex().upper()], -5)]
            )
