"""Tests for the PRODUCTION fp32 radix-2^8 Ed25519 kernel
(ops/ed25519_f32.py) — the kernel the gateway actually runs
(ops/gateway.py selects it on every backend).

Mirrors the rigorous coverage test_ops.py gives the int32 reference
kernel: RFC 8032 vectors, tampered sig/msg/pub, high-s, non-canonical R,
empty/odd/bucket-padded batches — plus field-arithmetic regression tests
for the two round-2 review findings (fcanon digit canonicality, fmul
exactness at loose-bound maxima).

Reference hot paths these semantics must match: per-signature verify at
/root/reference/types/vote_set.go:175 and the VerifyCommit loop at
/root/reference/types/validator_set.go:247-250.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.ops import ed25519_f32 as f32

P = f32.P


def _limbs_value(out: np.ndarray, lane: int) -> int:
    return sum(int(out[k, lane]) << (8 * k) for k in range(32))


class TestFieldArithmetic:
    def test_fcanon_digit_canonicality_adversarial(self):
        """Round-2 review (high): a parallel-only carry chain left limb0 at
        up to 293 for values < p, so the digit-wise equality in
        _verify_impl could falsely reject a valid signature. fcanon must
        emit digits strictly in [0, 256) for any loose input."""
        x = np.zeros((32, 4), dtype=np.float32)
        x[30, :] = 256.0
        x[31, :] = 255.0
        x[0, :] = [218, 230, 240, 255]
        out = np.asarray(f32.fcanon(jnp.asarray(x)))
        assert out.max() < 256.0 and out.min() >= 0.0
        for b in range(4):
            val = sum(int(x[k, b]) << (8 * k) for k in range(32))
            assert _limbs_value(out, b) == val % P

    def test_fcanon_loose_bound_extremes(self):
        cases = [
            np.full((32, 1), 268.0),
            np.full((32, 1), 825.0),
            np.zeros((32, 1)),
        ]
        cases[0][0, 0] = 825.0
        # exact p, 2p, p-1, p+1, 2p-1 as byte limbs
        for v in (0, P, 2 * P, P - 1, P + 1, 2 * P - 1):
            d = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
            cases.append(d.astype(np.float64).reshape(32, 1))
        for x in cases:
            out = np.asarray(f32.fcanon(jnp.asarray(x.astype(np.float32))))
            val = sum(int(x[k, 0]) << (8 * k) for k in range(32))
            assert out.max() < 256.0 and out.min() >= 0.0
            assert _limbs_value(out, 0) == val % P

    def test_fcanon_random_loose(self):
        rng = np.random.default_rng(7)
        x = rng.integers(0, 826, size=(32, 128)).astype(np.float32)
        out = np.asarray(f32.fcanon(jnp.asarray(x)))
        assert out.max() < 256.0 and out.min() >= 0.0
        for b in range(x.shape[1]):
            val = sum(int(x[k, b]) << (8 * k) for k in range(32))
            assert _limbs_value(out, b) == val % P

    def test_fmul_exact_at_loose_bound_maxima(self):
        """Round-2 review (low): fmul exactness rests on the active
        backend's HIGHEST-precision conv being exact for the documented
        integer ranges. Pin it: multiply limb vectors at the loose-bound
        maxima (and random loose values) and compare against python ints."""
        rng = np.random.default_rng(3)
        a = np.full((32, 8), 268.0)
        a[0, :] = 749.0
        b = np.full((32, 8), 268.0)
        b[0, :] = 825.0
        rand_a = rng.integers(0, 750, size=(32, 8)).astype(np.float64)
        rand_b = rng.integers(0, 826, size=(32, 8)).astype(np.float64)
        for lhs, rhs in [(a, b), (rand_a, rand_b)]:
            out = np.asarray(
                f32.fcanon(
                    f32.fmul(
                        jnp.asarray(lhs.astype(np.float32)),
                        jnp.asarray(rhs.astype(np.float32)),
                    )
                )
            )
            for lane in range(lhs.shape[1]):
                va = sum(int(lhs[k, lane]) << (8 * k) for k in range(32))
                vb = sum(int(rhs[k, lane]) << (8 * k) for k in range(32))
                assert _limbs_value(out, lane) == (va * vb) % P


# RFC 8032 §7.1 test vectors (secret, public, message, signature)
RFC8032_VECTORS = [
    (
        "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
        "",
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b",
    ),
    (
        "4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
        "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
        "72",
        "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
        "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00",
    ),
    (
        "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
        "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
        "af82",
        "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
        "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a",
    ),
]


class TestVerifyF32:
    def test_rfc8032_vectors(self):
        items = []
        for _sk, pk, msg, sig in RFC8032_VECTORS:
            items.append((bytes.fromhex(pk), bytes.fromhex(msg), bytes.fromhex(sig)))
        out = f32.verify_batch(items)
        assert list(out) == [True] * len(items)

    def test_tampered_and_malformed_lanes(self):
        """Mixed batch: valid, tampered sig, tampered msg, wrong pub,
        high-s, non-canonical R.y, bad-length pub/sig, invalid point —
        lane-exact against the CPU reference verifier."""
        seeds = [bytes([i + 1]) * 32 for i in range(8)]
        pubs = [ed.public_key(s) for s in seeds]
        msg = b"vote:height=7,round=0"
        sigs = [ed.sign(s, msg) for s in seeds]

        high_s = sigs[4][:32] + (
            (int.from_bytes(sigs[4][32:], "little") + ed.L).to_bytes(32, "little")
        )
        noncanon_r = (P + 1).to_bytes(32, "little") + sigs[5][32:]
        items = [
            (pubs[0], msg, sigs[0]),                                   # valid
            (pubs[1], msg, sigs[1][:10] + b"\x00" + sigs[1][11:]),      # tampered sig
            (pubs[2], msg + b"!", sigs[2]),                             # tampered msg
            (pubs[0], msg, sigs[3]),                                    # wrong pub
            (pubs[4], msg, high_s),                                     # s >= L
            (pubs[5], msg, noncanon_r),                                 # R.y >= p
            (pubs[6][:31], msg, sigs[6]),                               # short pub
            (pubs[7], msg, sigs[7] + b"\x00"),                          # long sig
            (b"\x01" * 32, msg, sigs[0]),                               # invalid point
            (pubs[3], msg, sigs[3]),                                    # valid again
        ]
        got = list(f32.verify_batch(items))
        want = [ed.verify(p, m, s) for p, m, s in items]
        assert got == want
        assert want == [True, False, False, False, False, False, False, False, False, True]

    def test_empty_odd_and_padded_batches(self):
        assert list(f32.verify_batch([])) == []
        seeds = [bytes([i + 10]) * 32 for i in range(5)]
        items = [
            (ed.public_key(s), b"m%d" % i, ed.sign(s, b"m%d" % i))
            for i, s in enumerate(seeds)
        ]
        # odd batch (5 -> bucket 8): padding lanes must not leak into results
        assert list(f32.verify_batch(items)) == [True] * 5
        items[2] = (items[2][0], items[2][1], items[2][2][:63] + b"\x00")
        out = list(f32.verify_batch(items))
        assert out == [True, True, False, True, True] or out == [
            ed.verify(p, m, s) for p, m, s in items
        ]

    def test_identical_keys_many_messages(self):
        """The commit shape: few validators, many (H,R) messages."""
        seed = b"\x42" * 32
        pub = ed.public_key(seed)
        items = [
            (pub, b"height=%d" % i, ed.sign(seed, b"height=%d" % i))
            for i in range(16)
        ]
        items[7] = (pub, items[7][1], items[3][2])  # sig for wrong message
        got = list(f32.verify_batch(items))
        assert got == [i != 7 for i in range(16)]


def _mixed_items():
    seeds = [bytes([i + 30]) * 32 for i in range(6)]
    items = [
        (ed.public_key(s), b"native-%d" % i, ed.sign(s, b"native-%d" % i))
        for i, s in enumerate(seeds)
    ]
    items.append((b"\x07" * 32, b"badpoint", items[0][2]))       # invalid A
    items.append((items[1][0][:16], b"shortpub", items[1][2]))    # bad length
    high_s = items[2][2][:32] + (
        (int.from_bytes(items[2][2][32:], "little") + ed.L).to_bytes(32, "little")
    )
    items.append((items[2][0], b"native-2", high_s))              # s >= L
    return items


class TestMarshalNativeParity:
    """The marshal has two implementations per stage (native C / python
    fallback); their outputs must be byte-identical."""

    def test_prepare_native_vs_python(self, monkeypatch):
        from tendermint_tpu import native

        if not native.available():
            pytest.skip("native library unavailable")
        items = _mixed_items()
        f32._pubkey_cache.clear()
        nat = f32.prepare_batch8(items, 16)
        f32._pubkey_cache.clear()
        monkeypatch.setattr(native, "available", lambda: False)
        pure = f32.prepare_batch8(items, 16)
        for a, b in zip(nat, pure):
            assert np.array_equal(a, b)
        f32._pubkey_cache.clear()

    def test_cache_warm_vs_cold_identical(self):
        items = _mixed_items()
        f32._pubkey_cache.clear()
        cold = f32.prepare_batch8(items, 16)
        warm = f32.prepare_batch8(items, 16)
        for a, b in zip(cold, warm):
            assert np.array_equal(a, b)


class TestGatewayAsync:
    def test_async_matches_sync_and_order(self):
        from tendermint_tpu.ops.gateway import Verifier

        v = Verifier(min_tpu_batch=4, use_tpu=True)
        batches = []
        for salt in range(3):
            seeds = [bytes([salt * 8 + i + 1]) * 32 for i in range(6)]
            b = [
                (ed.public_key(s), b"a%d-%d" % (salt, i), ed.sign(s, b"a%d-%d" % (salt, i)))
                for i, s in enumerate(seeds)
            ]
            b[salt] = (b[salt][0], b[salt][1], b"\x00" * 64)
            batches.append(b)
        resolvers = [v.verify_batch_async(b) for b in batches]
        results = [r() for r in resolvers]
        for salt, res in enumerate(results):
            assert res == [i != salt for i in range(6)]
        assert v.stats()["tpu_batches"] == 3

    def test_async_below_threshold_resolves_cpu(self):
        from tendermint_tpu.ops.gateway import Verifier

        v = Verifier(min_tpu_batch=64, use_tpu=True)
        seed = b"\x51" * 32
        items = [(ed.public_key(seed), b"small", ed.sign(seed, b"small"))]
        resolve = v.verify_batch_async(items)
        assert resolve() == [True]
        assert v.stats()["cpu_sigs"] == 1 and v.stats()["tpu_batches"] == 0

    def test_async_resolve_device_failure_falls_back(self, monkeypatch):
        """ADVICE r2 medium: device-side failures surface at
        materialization; resolve() must keep the CPU-fallback guarantee."""
        from tendermint_tpu.ops import gateway as gw

        class Boom:
            def __array__(self, *a, **k):
                raise RuntimeError("device lost")

            def __getitem__(self, k):
                raise RuntimeError("device lost")

        v = gw.Verifier(min_tpu_batch=1, use_tpu=True)
        seed = b"\x52" * 32
        items = [(ed.public_key(seed), b"m%d" % i, ed.sign(seed, b"m%d" % i)) for i in range(4)]
        monkeypatch.setattr(f32, "_verify_jit", lambda *a: Boom())
        resolve = v.verify_batch_async(items)
        assert resolve() == [True] * 4          # CPU fallback result
        assert v._tpu_ok is False               # permanent fallback latched
        stats = v.stats()
        assert stats["cpu_sigs"] == 4 and stats["tpu_sigs"] == 0


class TestKernelRegistry:
    """TENDERMINT_TPU_KERNEL selects the verify backend (gateway.KERNELS)."""

    def test_default_is_platform_aware(self, monkeypatch):
        from tendermint_tpu.ops import gateway as gw

        monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
        want = (
            "tendermint_tpu.ops.ed25519_comb"
            if gw.on_tpu()
            else "tendermint_tpu.ops.ed25519_f32"
        )
        assert gw.kernel_module().__name__ == want

    @pytest.mark.parametrize(
        "name,module",
        [
            ("f32p", "tendermint_tpu.ops.ed25519_f32p"),
            ("f32", "tendermint_tpu.ops.ed25519_f32"),
        ],
    )
    def test_selects_each_backend(self, monkeypatch, name, module):
        from tendermint_tpu.ops import gateway as gw

        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", name)
        assert gw.kernel_module().__name__ == module

    def test_unknown_name_fails_loudly(self, monkeypatch):
        from tendermint_tpu.ops import gateway as gw

        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", "cuda")
        with pytest.raises(ValueError, match="cuda"):
            gw.kernel_module()

    def test_async_without_pipelining_kernel_resolves_sync(self, monkeypatch):
        """Backends without verify_batch_async still honor the async API."""
        from tendermint_tpu.ops import gateway as gw

        v = gw.Verifier(min_tpu_batch=1, use_tpu=True)
        seed = b"\x53" * 32
        items = [
            (ed.public_key(seed), b"s%d" % i, ed.sign(seed, b"s%d" % i))
            for i in range(4)
        ]

        class SyncOnly:
            @staticmethod
            def verify_batch(its):
                return np.array([True] * len(its))

        monkeypatch.setattr(v, "_kernel_module", lambda: SyncOnly)
        resolve = v.verify_batch_async(items)
        assert resolve() == [True] * 4
        assert v.stats()["tpu_batches"] == 1

    def test_typo_fails_at_startup(self, monkeypatch):
        """A typo'd kernel name must fail at Verifier construction, not
        silently latch the CPU fallback at the first batch."""
        from tendermint_tpu.ops import gateway as gw

        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", "fp32")
        with pytest.raises(ValueError, match="fp32"):
            gw.Verifier(use_tpu=True)
        # with the TPU disabled outright the env var is irrelevant
        gw.Verifier(use_tpu=False)

    @pytest.mark.parametrize("name", ["int32", "pallas"])
    def test_a_deleted_kernels_name_fails_with_the_names_left(
            self, monkeypatch, name):
        from tendermint_tpu.ops import gateway as gw

        assert sorted(gw.KERNELS) == ["comb", "devd", "f32", "f32p"]
        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", name)
        with pytest.raises(ValueError, match=name) as err:
            gw.Verifier(use_tpu=True)
        assert str(sorted(gw.KERNELS)) in str(err.value)

    def test_kernel_by_argument_leaves_the_environment_alone(self, monkeypatch):
        """The daemon's claim names each candidate by argument: the
        verifier it gets dispatches to that kernel on the device only, and
        nothing is written into the process environment on the way."""
        import os

        from tendermint_tpu.ops import gateway as gw

        monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
        before = dict(os.environ)
        v = gw.Verifier(min_tpu_batch=1, use_tpu=True, host_fallback=False,
                        kernel="f32")
        assert v._kernel_module().__name__ == "tendermint_tpu.ops.ed25519_f32"
        seed = b"\x54" * 32
        items = [
            (ed.public_key(seed), b"k%d" % i, ed.sign(seed, b"k%d" % i))
            for i in range(4)
        ]
        assert v.verify_batch(items) == [True] * 4
        assert v.stats()["tpu_sigs"] == 4 and v.stats()["cpu_sigs"] == 0
        assert dict(os.environ) == before
        # the argument wins over the environment, and is validated like it
        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", "f32p")
        assert gw.Verifier(use_tpu=True, kernel="f32")._kernel == "f32"
        with pytest.raises(ValueError, match="int32"):
            gw.Verifier(use_tpu=True, kernel="int32")

    def test_sharded_rejects_non_f32(self, monkeypatch):
        import jax
        from jax.sharding import Mesh

        from tendermint_tpu.ops import gateway as gw

        monkeypatch.setenv("TENDERMINT_TPU_KERNEL", "comb")
        mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
        with pytest.raises(ValueError, match="comb"):
            gw.ShardedVerifier(mesh)


class TestPallasF32Kernel:
    """ops/ed25519_f32p — the pallas fp32 ladder (TPU-only: interpret
    mode on CPU is impractically slow for the 127-step unrolled kernel)."""

    @pytest.mark.tpu
    @pytest.mark.skipif(
        not __import__(
            "tendermint_tpu.ops.gateway", fromlist=["on_tpu"]
        ).on_tpu(),
        reason="pallas f32 kernel needs TPU hardware",
    )
    def test_parity_with_f32_including_malformed(self):
        from tendermint_tpu.ops import ed25519_f32p as f32p

        seeds = [bytes([i + 1]) * 32 for i in range(8)]
        items = []
        expected = []
        for i in range(64):
            s = seeds[i % 8]
            pk = ed.public_key(s)
            msg = b"p%d" % i
            sig = ed.sign(s, msg)
            ok = True
            if i % 5 == 1:
                sig = sig[:20] + bytes([sig[20] ^ 1]) + sig[21:]
                ok = False
            elif i % 5 == 2:
                # high-s: add L to the scalar half
                s_int = int.from_bytes(sig[32:], "little") + ed.L
                if s_int < 1 << 256:
                    sig = sig[:32] + s_int.to_bytes(32, "little")
                    ok = False
            elif i % 5 == 3:
                pk = b"\xff" * 32  # invalid pubkey
                ok = False
            items.append((pk, msg, sig))
            expected.append(ok)
        got = f32p.verify_batch(items)
        exp = np.array(expected)
        ref = np.asarray(f32.verify_batch(items))
        assert (got == exp).all()
        assert (got == ref).all()

    def test_registry_includes_f32p(self):
        from tendermint_tpu.ops import gateway as gw

        assert gw.KERNELS["f32p"] == "tendermint_tpu.ops.ed25519_f32p"

    def test_sharded_pins_f32_for_all_paths(self, monkeypatch):
        """Platform default must never swap ShardedVerifier onto the
        unsharded pallas kernel (sync OR async paths)."""
        import jax
        from jax.sharding import Mesh

        from tendermint_tpu.ops import gateway as gw

        monkeypatch.delenv("TENDERMINT_TPU_KERNEL", raising=False)
        mesh = Mesh(np.array(jax.devices()[:1]), ("batch",))
        sv = gw.ShardedVerifier(mesh)
        assert sv._kernel_module().__name__ == "tendermint_tpu.ops.ed25519_f32"


class TestCpuFallbackNative:
    """gateway._cpu_verify_batch rides the native C++ batch verifier for
    wide ed25519 batches; semantics must be identical to the per-item
    python loop on every edge case."""

    def test_parity_with_per_item_loop(self):
        from tendermint_tpu import native
        from tendermint_tpu.crypto.keys import verify_any
        from tendermint_tpu.ops.gateway import _cpu_verify_batch

        if not native.available():
            pytest.skip("native library unavailable")
        # ONLY 32/64-shaped items: a single off-length item would push the
        # whole batch onto the per-item path and make this test vacuous
        # (code-review r3) — the interesting edges (bad point, high-s,
        # tampered) are all shape-valid
        items = [
            it for it in _mixed_items() if len(it[0]) == 32 and len(it[2]) == 64
        ]
        seeds = [bytes([i + 50]) * 32 for i in range(16)]
        items += [
            (ed.public_key(s), b"pad-%d" % i, ed.sign(s, b"pad-%d" % i))
            for i, s in enumerate(seeds)
        ]
        assert len(items) >= 16
        exp = [verify_any(p, m, s) for p, m, s in items]
        assert exp.count(False) >= 2, "edge cases must be present"
        # the gateway path (which routes this shape through native)...
        got = _cpu_verify_batch(items)
        assert got == exp
        # ...and the native verifier DIRECTLY, so the comparison cannot
        # silently degrade to python-vs-python
        direct = native.ed25519_verify_batch(items)
        assert [bool(b) for b in direct] == exp

    def test_small_and_mixed_batches_stay_per_item(self):
        from tendermint_tpu.ops.gateway import _cpu_verify_batch

        seed = b"\x41" * 32
        small = [(ed.public_key(seed), b"s", ed.sign(seed, b"s"))]
        assert _cpu_verify_batch(small) == [True]
        # a secp-length key in the batch keeps the whole batch per-item
        mixed = small * 16 + [(b"\x02" * 33, b"m", b"\x00" * 64)]
        res = _cpu_verify_batch(mixed)
        assert res[:16] == [True] * 16 and res[16] is False
