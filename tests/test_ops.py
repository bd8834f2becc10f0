"""TPU ops tests (run on CPU backend; conftest forces an 8-device CPU mesh).

Parity contract: every kernel must reproduce the CPU implementation
byte-for-byte / verdict-for-verdict. These tests are the enforcement."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto.hashing import ripemd160
from tendermint_tpu.merkle.simple import (
    leaf_hash,
    simple_hash_from_byteslices,
    simple_proofs_from_hashes,
)
from tendermint_tpu.ops import ed25519 as ops_ed
from tendermint_tpu.ops import gateway
from tendermint_tpu.ops.hashing import ripemd160_batch, sha256_batch
from tendermint_tpu.ops.merkle import (
    leaf_hashes,
    part_leaf_hashes,
    tree_hash_from_leaf_digests,
)


class TestHashKernels:
    def test_ripemd160_parity(self):
        msgs = [b"", b"a", b"abc", b"x" * 200, bytes(range(256)) * 3, b"q" * 64]
        assert ripemd160_batch(msgs) == [ripemd160(m) for m in msgs]

    def test_sha256_parity(self):
        msgs = [b"", b"abc", b"z" * 1000]
        assert sha256_batch(msgs) == [hashlib.sha256(m).digest() for m in msgs]

    def test_empty_batch(self):
        assert ripemd160_batch([]) == []
        assert sha256_batch([]) == []


class TestMerkleKernel:
    @pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 16, 33, 100])
    def test_tree_and_proofs_parity(self, n):
        digests = [leaf_hash(b"item-%d" % i) for i in range(n)]
        root_cpu, proofs_cpu = simple_proofs_from_hashes(digests)
        root_tpu, aunts_tpu = tree_hash_from_leaf_digests(digests)
        assert root_tpu == root_cpu
        for i in range(n):
            assert aunts_tpu[i] == proofs_cpu[i].aunts

    def test_part_leaves(self):
        chunks = [bytes([i]) * (100 + i) for i in range(20)]
        assert part_leaf_hashes(chunks) == [ripemd160(c) for c in chunks]

    def test_leaf_hashes(self):
        items = [b"tx-%d" % i for i in range(9)]
        assert leaf_hashes(items) == [leaf_hash(i) for i in items]


@pytest.mark.slow
class TestFieldArithmetic:
    """int32 radix-2^15 field math under ops/ed25519.dsm_batch (the
    aggregate-commit lanes). Marked slow: compiles the multiply graphs."""

    def test_mul_inv_canon(self):
        import random

        random.seed(7)
        vals = [random.randrange(ref.P) for _ in range(8)]
        bv = [random.randrange(ref.P) for _ in range(8)]
        aj = jnp.asarray(ops_ed.int_to_limbs_np(vals))
        bj = jnp.asarray(ops_ed.int_to_limbs_np(bv))
        mres = np.asarray(jax.jit(lambda a, b: ops_ed.fcanon(ops_ed.fmul(a, b)))(aj, bj))
        for i in range(8):
            assert ops_ed.limbs_to_int(mres[:, i]) == (vals[i] * bv[i]) % ref.P

    def test_edge_values(self):
        edge = [0, 1, ref.P - 1, ref.P - 19, 2**255 - 20, (1 << 255) - 1]
        aj = jnp.asarray(ops_ed.int_to_limbs_np(edge))
        out = np.asarray(jax.jit(lambda a: ops_ed.fcanon(ops_ed.fmul(a, a)))(aj))
        for i, v in enumerate(edge):
            assert ops_ed.limbs_to_int(out[:, i]) == (v * v) % ref.P


def _mk_items(n, corrupt=()):
    items = []
    for i in range(n):
        sk = hashlib.sha256(b"t%d" % i).digest()
        pub = ref.public_key(sk)
        msg = b"msg-%d" % i
        sig = ref.sign(sk, msg)
        items.append((pub, msg, sig))
    for i, kind in corrupt:
        pub, msg, sig = items[i]
        if kind == "sig":
            b = bytearray(sig)
            b[0] ^= 1
            items[i] = (pub, msg, bytes(b))
        elif kind == "msg":
            items[i] = (pub, b"evil", sig)
        elif kind == "pub":
            b = bytearray(pub)
            b[0] ^= 1
            items[i] = (bytes(b), msg, sig)
        elif kind == "high_s":
            s = int.from_bytes(sig[32:], "little") + ref.L
            items[i] = (pub, msg, sig[:32] + s.to_bytes(32, "little"))
    return items


class TestGateway:
    def test_tx_root_hook_parity(self):
        """The node-assembly hook (types/tx.set_batch_tx_root) must route
        Txs.Hash through the batched kernel with a byte-identical root
        (ref types/tx.go:33-46) and move the hasher stats."""
        from tendermint_tpu.merkle.simple import simple_hash_from_hashes
        from tendermint_tpu.types import tx as tx_types

        txs = [bytes([i]) * (i + 1) for i in range(20)]
        # explicit CPU reference — independent of any hook a previously
        # constructed Node may have left installed in this process
        cpu_root = simple_hash_from_hashes([tx_types.tx_hash(t) for t in txs])
        hasher = gateway.Hasher(min_tpu_batch=1, use_tpu=True)
        prev = tx_types._batch_tx_root
        tx_types.set_batch_tx_root(hasher.tx_merkle_root)
        try:
            tpu_root = tx_types.txs_hash(txs)
        finally:
            tx_types.set_batch_tx_root(prev)
        assert tpu_root == cpu_root
        st = hasher.stats()
        assert st["tpu_tx_roots"] == 1 and st["tpu_leaves"] == 20

    def test_cpu_small_batch(self):
        v = gateway.Verifier(min_tpu_batch=1000)
        items = _mk_items(4, corrupt=[(2, "sig")])
        assert v.verify_batch(items) == [True, True, False, True]
        assert v.stats()["cpu_sigs"] == 4

    def test_tpu_path_parity(self):
        v = gateway.Verifier(min_tpu_batch=1)
        items = _mk_items(8, corrupt=[(0, "sig")])
        assert v.verify_batch(items) == [False] + [True] * 7

    def test_verify_one(self):
        v = gateway.Verifier()
        (pub, msg, sig) = _mk_items(1)[0]
        assert v.verify_one(pub, msg, sig)
        assert not v.verify_one(pub, b"other", sig)

    def test_hasher_transport_keyed_policy(self, monkeypatch):
        """Hasher default offloads iff the device's OWNER reports a
        dispatch round trip at local-chip scale (the daemon's ping): a
        process that is not the daemon never measures one itself."""
        from tendermint_tpu import devd

        monkeypatch.delenv("TENDERMINT_TPU_HASHES", raising=False)
        monkeypatch.delenv("TENDERMINT_TPU_DISABLE", raising=False)

        def daemon_says(rtt):
            rep = None if rtt == "absent" else {"held": True, "platform": "tpu"}
            if rep is not None and rtt is not None:
                rep["rtt_ms"] = rtt
            monkeypatch.setattr(devd, "available", lambda *a, **k: rep)

        daemon_says(2.0)
        assert gateway.Hasher()._tpu_ok  # local-chip rtt -> offload
        assert gateway.Hasher().route() == "devd"
        daemon_says(90.0)
        assert not gateway.Hasher()._tpu_ok  # slow transport -> host
        daemon_says(None)
        assert not gateway.Hasher()._tpu_ok  # daemon reports none -> host
        assert gateway.Hasher().route() == "host"
        daemon_says("absent")
        assert not gateway.Hasher()._tpu_ok  # no daemon -> host
        monkeypatch.setenv("TENDERMINT_TPU_HASHES", "1")
        assert gateway.Hasher()._tpu_ok  # forced on beats transport
        monkeypatch.setenv("TENDERMINT_TPU_HASHES", "0")
        daemon_says(2.0)
        assert not gateway.Hasher()._tpu_ok  # forced off beats transport

    def test_hasher_fallback_parity(self):
        # use_tpu=True explicitly: the Hasher default is transport-keyed
        # (CPU on this boxed test env), which would make this
        # kernel-parity check compare CPU to CPU
        h_tpu = gateway.Hasher(min_tpu_batch=1, use_tpu=True)
        h_cpu = gateway.Hasher(min_tpu_batch=10**9)
        chunks = [b"c%d" % i * 50 for i in range(8)]
        assert h_tpu.part_leaf_hashes(chunks) == h_cpu.part_leaf_hashes(chunks)
        txs = [b"tx%d" % i for i in range(8)]
        assert h_tpu.tx_merkle_root(txs) == h_cpu.tx_merkle_root(txs)
        assert h_cpu.tx_merkle_root(txs) == simple_hash_from_byteslices(txs)


class TestShardedVerifier:
    def test_mesh_sharded_batch(self):
        """Multi-chip path: batch axis sharded over the 8-device CPU mesh."""
        from jax.sharding import Mesh

        devs = np.array(jax.devices())
        assert devs.size == 8, "conftest should force 8 cpu devices"
        mesh = Mesh(devs, ("batch",))
        v = gateway.ShardedVerifier(mesh, min_tpu_batch=1)
        items = _mk_items(16, corrupt=[(5, "sig")])
        out = v.verify_batch(items)
        assert out == [True] * 5 + [False] + [True] * 10
        assert v.stats()["tpu_sigs"] == 16

    def test_mesh_sharded_f32p_parity(self, monkeypatch):
        """The f32p ladder sharded 8 ways (ed25519_f32p.make_sharded_verify):
        on this CPU mesh the per-shard body is the plain-XLA _ladder — the
        exact math the pallas kernel runs per chip on a TPU mesh — so this
        is a real parity check of the sharded f32p path (VERDICT r3 #3)."""
        from jax.sharding import Mesh

        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", "f32p")
        devs = np.array(jax.devices())
        mesh = Mesh(devs, ("batch",))
        v = gateway.ShardedVerifier(mesh, min_tpu_batch=1)
        assert v._kernel == "f32p"
        items = _mk_items(16, corrupt=[(3, "sig"), (11, "msg")])
        out = v.verify_batch(items)
        assert out == [i not in (3, 11) for i in range(16)]
        assert v.stats()["tpu_sigs"] == 16
        assert v._kernel == "f32p"  # did not silently demote to f32

    def test_sharded_async_uses_the_sharded_path(self):
        """verify_batch_async on a ShardedVerifier must ride the sharded
        dispatch (regression: the inherited base implementation silently
        ran the UNSHARDED kernel)."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()), ("batch",))
        v = gateway.ShardedVerifier(mesh, min_tpu_batch=1)
        items = _mk_items(16, corrupt=[(9, "msg")])
        resolve = v.verify_batch_async(items)
        assert resolve() == [i != 9 for i in range(16)]
        assert v.stats()["tpu_batches"] == 1
        assert v.stats()["tpu_sigs"] == 16

    def test_sharded_rejects_bakeoff_kernels(self, monkeypatch):
        from jax.sharding import Mesh

        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", "comb")
        mesh = Mesh(np.array(jax.devices()), ("batch",))
        with pytest.raises(ValueError, match="shards the f32/f32p"):
            gateway.ShardedVerifier(mesh)

    def test_sharded_fast_sync_commit(self):
        """Fast sync's VerifyCommit driven end-to-end through the sharded
        verifier: real ValidatorSet commits (the quorum math of
        types/validator_set.go:220-264) grouped exactly as the blockchain
        reactor groups them (validator_set.verify_commits_async — the
        call blockchain/reactor._dispatch_speculative makes, replacing
        the reference's per-block loop at blockchain/reactor.go:235-236),
        with the signature batch sharded over the 8-device CPU mesh.
        Asserts verdicts AND the measured per-device shard layout."""
        from jax.sharding import Mesh

        from tendermint_tpu.types.validator_set import CommitError
        from tests.test_types import BLOCK_ID, make_val_set, signed_vote
        from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT
        from tendermint_tpu.types.vote_set import VoteSet

        vs, privs = make_val_set(8, power=1)
        entries = []
        for height in (1, 2, 3):
            voteset = VoteSet(
                "test-chain", height, 0, VOTE_TYPE_PRECOMMIT, vs
            )
            for p in privs:
                voteset.add_vote(
                    signed_vote(p, vs, height, 0, VOTE_TYPE_PRECOMMIT, BLOCK_ID)
                )
            entries.append((BLOCK_ID, height, voteset.make_commit()))
        # tamper height 2's first signature: its finisher (and ONLY its
        # finisher) must raise, as the reactor's bad-block path expects
        from tendermint_tpu.crypto.keys import SignatureEd25519

        bad = entries[1][2]
        bad.precommits[0] = bad.precommits[0].with_signature(
            SignatureEd25519(b"\x07" * 64)
        )

        mesh = Mesh(np.array(jax.devices()), ("batch",))
        v = gateway.ShardedVerifier(mesh, min_tpu_batch=1)
        finishers = vs.verify_commits_async(
            "test-chain", entries, v.verify_batch_async
        )
        assert len(finishers) == 3
        finishers[0]()
        with pytest.raises(CommitError):
            finishers[1]()
        finishers[2]()
        # one grouped dispatch, 24 signatures, sharded over all 8 devices
        assert v.stats()["tpu_batches"] == 1
        assert v.stats()["tpu_sigs"] == 24
        layout = v.last_shard_layout
        assert layout is not None and len(layout) == 8, layout
        assert len({d for d, _ in layout}) == 8, layout
        assert len({sz for _, sz in layout}) == 1, layout
