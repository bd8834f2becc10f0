"""Tests for the doubling-free comb Ed25519 kernel (ops/ed25519_comb.py)
— per-validator device-resident comb tables + fixed-base MXU comb.

Same coverage discipline as test_ops_f32.py (the kernel contract is
identical: strict cofactorless RFC 8032, lane-for-lane parity with
crypto/ed25519.verify), plus the pool mechanics that are new here:
slot reuse across batches, LRU eviction, capacity growth, and the
PoolExhausted -> ladder fallback.

Reference hot paths: types/vote_set.go:175,
types/validator_set.go:247-250, blockchain/reactor.go:235.
"""

from __future__ import annotations

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.ops import ed25519_comb as comb


@pytest.fixture(autouse=True)
def _fresh_pool(monkeypatch):
    # build tables on first sight so every test below exercises the comb
    # path; the second-sight production default gets its own test
    monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "1")
    comb.reset_default_pool()
    yield
    comb.reset_default_pool()


def _keypair(rng):
    sk = rng.bytes(32)
    return sk, ed.public_key(sk)


def _signed(rng, sk, pk, n=1, msg_len=40):
    out = []
    for _ in range(n):
        m = rng.bytes(msg_len)
        out.append((pk, m, ed.sign(sk, m)))
    return out


class TestVerifyParity:
    def test_rfc8032_vectors(self):
        # RFC 8032 section 7.1 test vectors 1-3
        vecs = [
            (
                "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
                b"",
            ),
            (
                "4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
                bytes([0x72]),
            ),
            (
                "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
                bytes([0xAF, 0x82]),
            ),
        ]
        items = []
        for sk_hex, msg in vecs:
            sk = bytes.fromhex(sk_hex)
            pk = ed.public_key(sk)
            items.append((pk, msg, ed.sign(sk, msg)))
        assert list(comb.verify_batch(items)) == [True, True, True]

    def test_parity_with_cpu_reference_mixed_batch(self):
        """Random valid sigs from several keys, tampered sig/msg/pub,
        non-canonical s, bad-length rows — lane-for-lane identical to
        crypto/ed25519.verify."""
        rng = np.random.default_rng(11)
        pairs = [_keypair(rng) for _ in range(4)]
        items = []
        for i in range(24):
            sk, pk = pairs[i % 4]
            m = rng.bytes(32 + i)
            sig = ed.sign(sk, m)
            if i % 6 == 1:  # tamper sig
                b = bytearray(sig)
                b[10] ^= 0x40
                sig = bytes(b)
            elif i % 6 == 2:  # tamper msg
                m = m[:-1] + bytes([m[-1] ^ 1])
            elif i % 6 == 3:  # wrong pubkey
                pk = pairs[(i + 1) % 4][1]
            elif i % 6 == 4:  # non-canonical s (s + L)
                s_int = int.from_bytes(sig[32:], "little") + ed.L
                sig = sig[:32] + s_int.to_bytes(32, "little")
            items.append((pk, m, sig))
        items.append((b"\x00" * 31, b"m", b"\x00" * 64))  # bad pub length
        items.append((pairs[0][1], b"m", b"\x00" * 63))  # bad sig length
        expect = [ed.verify(p, m, s) for p, m, s in items]
        assert list(comb.verify_batch(items)) == expect

    def test_empty_and_single(self):
        rng = np.random.default_rng(3)
        sk, pk = _keypair(rng)
        assert list(comb.verify_batch([])) == []
        (it,) = _signed(rng, sk, pk)
        assert list(comb.verify_batch([it])) == [True]

    def test_agrees_with_f32_kernel(self):
        from tendermint_tpu.ops import ed25519_f32 as f32

        rng = np.random.default_rng(7)
        pairs = [_keypair(rng) for _ in range(3)]
        items = []
        for i in range(12):
            sk, pk = pairs[i % 3]
            m = rng.bytes(20)
            sig = ed.sign(sk, m)
            if i % 4 == 3:
                sig = sig[:63] + bytes([sig[63] ^ 2])
            items.append((pk, m, sig))
        assert list(comb.verify_batch(items)) == list(f32.verify_batch(items))


class TestPool:
    def test_slot_reuse_across_batches(self):
        rng = np.random.default_rng(5)
        sk, pk = _keypair(rng)
        comb.verify_batch(_signed(rng, sk, pk, 3))
        pool = comb.default_pool()
        assert pool.stats["build_keys"] == 1
        comb.verify_batch(_signed(rng, sk, pk, 3))
        assert pool.stats["build_keys"] == 1  # no rebuild on reuse

    def test_growth_and_eviction(self):
        pool = comb.CombPool(capacity=2, max_capacity=4)
        comb.set_default_pool(pool)
        rng = np.random.default_rng(9)
        pairs = [_keypair(rng) for _ in range(5)]
        assert pool.capacity == 2  # starts small
        for sk, pk in pairs[:3]:
            assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]
        assert pool.capacity == pool.cap == 4  # grew (slot 0 reserved)
        assert pool.stats["grows"] == 1
        # 2 more distinct keys -> evictions, results still correct
        for sk, pk in pairs[3:]:
            assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]
        assert pool.stats["evictions"] >= 1
        # the evicted first key still verifies correctly after re-lease
        sk, pk = pairs[0]
        assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]

    def test_second_sight_policy(self, monkeypatch):
        """Production default: a key's table is built only on its second
        batch appearance — first sight rides the ladder (one-shot mempool
        keys never pay the ~13-verify build; validator keys, which sign
        every block, are all-comb from block two)."""
        monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "2")
        comb.reset_default_pool()
        rng = np.random.default_rng(21)
        sk, pk = _keypair(rng)
        pool = comb.default_pool()
        assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]
        assert pool.stats["build_keys"] == 0  # first sight: ladder
        assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]
        assert pool.stats["build_keys"] == 1  # second sight: built
        assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]
        assert pool.stats["build_keys"] == 1  # reused thereafter

    def test_pool_exhausted_falls_back_to_ladder(self, monkeypatch):
        monkeypatch.setenv("TENDERMINT_TPU_COMB_CAP", "2")
        comb.reset_default_pool()
        rng = np.random.default_rng(13)
        pairs = [_keypair(rng) for _ in range(3)]
        items = []
        for sk, pk in pairs:  # 3 distinct keys > 1 usable slot (cap=2)
            items.extend(_signed(rng, sk, pk))
        out = comb.verify_batch(items)  # must not raise
        assert list(out) == [True, True, True]
        # round-5 review regression: the aborted lease must be rolled
        # back — a follow-up batch with one of those keys must not ride a
        # never-built (garbage) slot table and reject a valid signature
        for sk, pk in pairs:
            assert list(comb.verify_batch(_signed(rng, sk, pk))) == [True]

    def test_eviction_never_steals_from_current_batch(self, monkeypatch):
        """Round-5 design bug guard: assigning slots for one batch must
        not evict a slot already leased to an earlier lane of the SAME
        batch (the earlier lane would verify against the wrong table)."""
        monkeypatch.setenv("TENDERMINT_TPU_COMB_CAP", "4")
        comb.reset_default_pool()
        rng = np.random.default_rng(17)
        # 3 distinct keys fill the 3 usable slots in one batch; then a
        # 4th-key batch triggers eviction of an out-of-batch slot only
        pairs = [_keypair(rng) for _ in range(4)]
        items = []
        for sk, pk in pairs[:3]:
            items.extend(_signed(rng, sk, pk, 2))
        assert all(comb.verify_batch(items))
        items2 = []
        for sk, pk in pairs[1:]:  # keys 1,2 pinned + new key 3
            items2.extend(_signed(rng, sk, pk, 2))
        assert all(comb.verify_batch(items2))


class TestBTable:
    def test_b_table_first_window_matches_reference(self):
        tab = comb.b_table()
        # entry [0][1] is 1*B: niels rows of the base point
        bx, by = ed.B[0], ed.B[1]
        want = comb._niels_rows_np(bx, by)
        assert np.array_equal(tab[0, 1], want)
        # entry [p][0] is the identity in niels form
        ident = np.zeros(96, dtype=np.float32)
        ident[0] = 1.0
        ident[32] = 1.0
        assert np.array_equal(tab[5, 0], ident)

    def test_b_table_window_weights(self):
        tab = comb.b_table()
        # entry [1][1] must be 16*B
        acc = ed.B
        for _ in range(4):
            acc = ed.point_double(acc)
        x, y = comb.base._affine(acc)
        assert np.array_equal(tab[1, 1], comb._niels_rows_np(x, y))


def _reference_tables(points) -> np.ndarray:
    """(n, 1024, 96) niels tables of v * 16^p * Q for extended points Q,
    in Python integers: the build's contract, computed the plain way."""
    ident = np.zeros(comb.COORD_ROWS, dtype=np.float32)
    ident[0] = ident[comb.NL] = 1.0
    out = np.zeros((len(points), comb.W_POS, comb.W_ENT, comb.COORD_ROWS),
                   dtype=np.float32)
    for i, q in enumerate(points):
        for p in range(comb.W_POS):
            out[i, p, 0] = ident
            acc = q
            for v in range(1, comb.W_ENT):
                out[i, p, v] = comb._niels_rows_np(*comb.base._affine(acc))
                acc = ed.point_add(acc, q)
            for _ in range(4):
                q = ed.point_double(q)
    return out.reshape(len(points), comb.W_POS * comb.W_ENT, comb.COORD_ROWS)


def _neg_point(pub: bytes):
    """-A of the key `pub` as an extended point."""
    x, y = comb.base._affine(ed.point_decompress(pub))
    x = (-x) % comb.P
    return (x, y, 1, x * y % comb.P)


def _neg_points(rng, n):
    """n random keys' -A as extended points, and the build's (32, n) limb
    columns of them."""
    pts = [_neg_point(_keypair(rng)[1]) for _ in range(n)]
    qx = np.stack([comb.base._int_to_limbs_const(p[0]) for p in pts], axis=1)
    qy = np.stack([comb.base._int_to_limbs_const(p[1]) for p in pts], axis=1)
    return pts, qx, qy


def _build(qx, qy) -> np.ndarray:
    import jax

    return np.asarray(jax.jit(comb._build_tables_impl)(qx, qy))


class TestTableBuild:
    """_build_tables_impl against Python integers, limb for limb, on the
    CPU backend: the tables a key's later verifications read."""

    @pytest.mark.parametrize("n", [1, 3])
    def test_tables_match_python_ints(self, n):
        pts, qx, qy = _neg_points(np.random.default_rng(40 + n), n)
        assert np.array_equal(_build(qx, qy), _reference_tables(pts))

    def test_bucket_padded_with_edge_repeats(self):
        """The open pool's form: 3 keys padded to a bucket of 8 by
        repeating the last; the padding lanes hold the last key's table."""
        pts, qx, qy = _neg_points(np.random.default_rng(43), 3)
        pad = ((0, 0), (0, 5))
        got = _build(np.pad(qx, pad, mode="edge"),
                     np.pad(qy, pad, mode="edge"))
        assert np.array_equal(got, _reference_tables(pts + [pts[-1]] * 5))

    def test_passes_past_build_keys(self, monkeypatch):
        """More keys than BUILD_KEYS run as passes of that many, the last
        one padded inside the program: 5 keys in passes of 2."""
        monkeypatch.setattr(comb, "BUILD_KEYS", 2)
        pts, qx, qy = _neg_points(np.random.default_rng(44), 5)
        assert np.array_equal(_build(qx, qy), _reference_tables(pts))

    def test_base_point_reproduces_b_table(self):
        qx = np.asarray(comb.base._BX, dtype=np.float32)[:, None]
        qy = np.asarray(comb.base._BY, dtype=np.float32)[:, None]
        got = _build(qx, qy).reshape(comb.W_POS, comb.W_ENT, comb.COORD_ROWS)
        assert np.array_equal(got, comb.b_table())


def _sequential_steps(jaxpr) -> int:
    """Loop steps that run one after another: scan lengths, a scan inside
    a scan multiplied; a while loop has no static trip count here (a
    fori_loop over static bounds is a scan), so none may appear."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        assert name != "while", "a loop with no static trip count"
        if name == "scan":
            total += eqn.params["length"] * max(
                1, _sequential_steps(eqn.params["jaxpr"].jaxpr))
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += _sequential_steps(inner)
    return total


def test_build_is_a_few_hundred_sequential_steps():
    """The mechanism of PR 36: the parent's build ran 3,184 loop steps in
    series (the chip then charged 38 ms for 1 key or 128); the work now
    lies on the lane axis and the chain is 428 steps at the bucket."""
    import jax
    import jax.numpy as jnp

    q = jax.ShapeDtypeStruct((comb.NL, comb.MISS_BUCKET), jnp.float32)
    jaxpr = jax.make_jaxpr(comb._build_tables_impl)(q, q).jaxpr
    steps = _sequential_steps(jaxpr)
    assert steps <= 600, steps


class TestPoolLayout:
    """The pool is (C*64, 1536) bf16, a row a (slot, window position) with
    its 16 entries: the bytes of the (C*1024, 96) form, in its order, but
    a shape the v5e stores row-major and unpadded, so that the comb
    program gathers rows from the pool where it lies
    (tests/test_chip_compile.py holds the compiled program to that)."""

    @pytest.mark.parametrize("is_open", [False, True])
    def test_a_slots_rows_are_its_reference_table(self, is_open):
        rng = np.random.default_rng(60 + is_open)
        pairs = [_keypair(rng) for _ in range(3)]
        pool = comb.CombPool(capacity=8, max_capacity=8, open_pop=is_open)
        comb.set_default_pool(pool)
        items = [it for sk, pk in pairs for it in _signed(rng, sk, pk)]
        assert all(comb.verify_batch(items))
        assert pool._pool.shape == (8 * comb.W_POS, comb.POOL_ROW)
        arr = np.asarray(pool._pool)
        want = _reference_tables([_neg_point(pk) for _sk, pk in pairs])
        for (_sk, pk), table in zip(pairs, want):
            slot = pool._lru[pk]
            rows = arr[slot * comb.W_POS:(slot + 1) * comb.W_POS]
            assert rows.reshape(comb.W_POS * comb.W_ENT, comb.COORD_ROWS
                                ).tobytes() == table.astype(arr.dtype).tobytes()

    @pytest.mark.parametrize("width", [1, 8, 128])
    def test_the_entries_a_lane_reads_are_the_flat_pools(self, width):
        """_pool_entries against a gather of single entries from the same
        bytes viewed as (C*1024, 96): every lane, padding lanes on slot 0
        among them, every digit."""
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(80 + width)
        cap = 6
        pool = jnp.asarray(rng.integers(0, 256, (cap * comb.W_POS,
                                                 comb.POOL_ROW)),
                           dtype=jnp.bfloat16)
        slots = rng.integers(0, cap, width).astype(np.int32)
        slots[-1] = 0
        dh = rng.integers(0, comb.W_ENT, (comb.W_POS, width)).astype(np.int32)
        got = jax.jit(comb._pool_entries)(pool, slots, dh)
        flat = np.asarray(pool, dtype=np.float32).reshape(-1, comb.COORD_ROWS)
        pos = np.arange(comb.W_POS)[:, None]
        want = flat[(slots[None, :] * comb.W_POS + pos) * comb.W_ENT + dh]
        assert np.array_equal(np.asarray(got), want.transpose(0, 2, 1))
