"""Direct tests for the small wire/runtime helpers that were previously
covered only transitively: codec/jsonval (the attacker-facing JSON bounds
contract), libs/flowrate, p2p/peer_set, types/protobuf (TM2PB).
Reference models: go-wire's size-capped decoding, tmlibs/flowrate,
p2p/peer_set.go, types/protobuf.go.
"""

from __future__ import annotations

import threading
import time

import pytest

from tendermint_tpu.codec import jsonval as jv


class TestJsonval:
    """Every violation must raise ValueError (the p2p receive paths turn
    that into a peer disconnect) — never crash, never allocate unbounded."""

    def test_int_field_accepts_range(self):
        assert jv.int_field({"h": 5}, "h", 0, 10) == 5
        assert jv.int_field({"h": 0}, "h", 0, jv.MAX_HEIGHT) == 0
        assert jv.int_field({"h": jv.MAX_HEIGHT}, "h", 0, jv.MAX_HEIGHT) == jv.MAX_HEIGHT

    @pytest.mark.parametrize("bad", [
        {"h": -1}, {"h": 11}, {"h": "5"}, {"h": 5.0}, {"h": None},
        {"h": True},  # bool is an int subclass; must still be rejected
        {"h": [5]}, {}, None, "not-a-dict", 7,
    ])
    def test_int_field_rejects(self, bad):
        with pytest.raises(ValueError):
            jv.int_field(bad, "h", 0, 10)

    def test_hex_field_roundtrip_and_caps(self):
        assert jv.hex_field({"x": "00ff"}, "x") == b"\x00\xff"
        assert jv.hex_field({"x": ""}, "x") == b""
        # exactly at the cap is fine; one byte over is rejected BEFORE
        # decoding (no attacker-sized allocation)
        assert jv.hex_field({"x": "ab" * 64}, "x") == b"\xab" * 64
        with pytest.raises(ValueError):
            jv.hex_field({"x": "ab" * 65}, "x")

    @pytest.mark.parametrize("bad", [
        {"x": "zz"}, {"x": "abc"}, {"x": 5}, {"x": None}, {"x": b"ab"},
        {}, None,
    ])
    def test_hex_field_rejects(self, bad):
        with pytest.raises(ValueError):
            jv.hex_field(bad, "x")

    def test_dict_field(self):
        assert jv.dict_field({"d": {"k": 1}}, "d") == {"k": 1}
        for bad in ({"d": []}, {"d": None}, {"d": "x"}, {}, None):
            with pytest.raises(ValueError):
                jv.dict_field(bad, "d")


class TestFlowrate:
    def test_status_tracks_totals_and_avg(self):
        from tendermint_tpu.libs.flowrate import Monitor

        m = Monitor(sample_period=0.01)
        for _ in range(10):
            m.update(1000)
            time.sleep(0.002)
        st = m.status()
        assert st.bytes == 10_000
        assert st.avg_rate > 0
        m.update(1000)
        assert m.status().bytes == 11_000

    def test_limit_paces_average_rate(self):
        from tendermint_tpu.libs.flowrate import Monitor

        m = Monitor()
        t0 = time.monotonic()
        sent = 0
        while sent < 3000:
            # each transfer waits for its instant under a 10 KB/s cap
            wait = m.not_before(rate_limit=10_000) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            m.update(1000)
            sent += 1000
        elapsed = time.monotonic() - t0
        # 3 KB at 10 KB/s floor: >= ~0.2s (pacing happened); uncapped this
        # loop finishes in microseconds
        assert elapsed >= 0.15, elapsed
        # 0 = unlimited: no wait, whatever was sent
        assert m.not_before(rate_limit=0) <= time.monotonic()


class _P:
    def __init__(self, pid):
        self._pid = pid

    def id(self):
        return self._pid


class TestPeerSet:
    def test_add_get_remove(self):
        from tendermint_tpu.p2p.peer_set import PeerSet

        ps = PeerSet()
        a, b = _P("aa"), _P("bb")
        assert ps.add(a) and ps.add(b)
        assert not ps.add(_P("aa"))  # duplicate id refused
        assert ps.has("aa") and ps.get("bb") is b
        assert ps.size() == 2 and set(p.id() for p in ps.list()) == {"aa", "bb"}
        ps.remove(a)
        assert not ps.has("aa") and ps.size() == 1
        ps.remove(a)  # idempotent

    def test_cap_enforced_under_concurrent_adds(self):
        """The cap check shares the registration lock: a 32-thread dial
        burst against cap=8 admits exactly 8 (p2p/peer_set.go's
        goroutine-safety contract; wired to max_num_peers in the switch)."""
        from tendermint_tpu.p2p.peer_set import PeerSet

        ps = PeerSet()
        admitted = []
        barrier = threading.Barrier(32)

        def dial(i):
            barrier.wait()
            if ps.add(_P("p%02d" % i), cap=8):
                admitted.append(i)

        threads = [threading.Thread(target=dial, args=(i,)) for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(admitted) == 8 and ps.size() == 8


class TestTM2PB:
    def test_header_conversion(self):
        from tendermint_tpu.types.block import Header
        from tendermint_tpu.types.block_id import BlockID
        from tendermint_tpu.types.protobuf import tm2pb_header

        h = Header(
            chain_id="pbchain", height=9, time_ns=123, num_txs=4,
            last_block_id=BlockID(), last_commit_hash=b"", data_hash=b"",
            validators_hash=b"", app_hash=b"\x0a" * 20,
        )
        ah = tm2pb_header(h)
        assert (ah.chain_id, ah.height, ah.time_ns, ah.num_txs, ah.app_hash) == (
            "pbchain", 9, 123, 4, b"\x0a" * 20,
        )

    def test_validator_conversions(self):
        from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
        from tendermint_tpu.types.protobuf import tm2pb_validator, tm2pb_validators
        from tendermint_tpu.types.validator import Validator

        pv = gen_priv_key_ed25519(b"\x3c" * 32)
        val = Validator.new(pv.pub_key(), 7)
        av = tm2pb_validator(val)
        assert av.power == 7 and av.pub_key_json == val.pub_key.to_json()

        class GV:  # genesis-doc validator shape
            def __init__(self, pk, power):
                self.pub_key = pk
                self.power = power

        out = tm2pb_validators([GV(pv.pub_key(), 3)])
        assert len(out) == 1 and out[0].power == 3
