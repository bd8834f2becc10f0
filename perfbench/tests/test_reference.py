"""The plain references: RFC 8032's own vectors, and the program's host
signer as a second witness."""

from reference import ed25519_ref as ref
from reference import kv_ref


def test_rfc8032_vectors():
    # RFC 8032 section 7.1, TEST 1 and TEST 2
    sk = bytes.fromhex("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60")
    pk = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b")
    assert ref.public_key(sk) == pk
    assert ref.sign(sk, b"") == sig
    assert ref.verify(pk, b"", sig)
    sk2 = bytes.fromhex("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb")
    pk2 = bytes.fromhex("3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c")
    sig2 = bytes.fromhex(
        "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
        "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00")
    assert ref.public_key(sk2) == pk2
    assert ref.sign(sk2, b"\x72") == sig2
    assert ref.verify(pk2, b"\x72", sig2)


def test_rejects_what_it_should():
    sk = bytes(range(32))
    pk, msg = ref.public_key(sk), b"perfbench"
    sig = ref.sign(sk, msg)
    assert ref.verify(pk, msg, sig)
    assert not ref.verify(pk, msg + b" ", sig)
    assert not ref.verify(pk, msg, sig[:9] + bytes([sig[9] ^ 0x10]) + sig[10:])
    assert not ref.verify(pk, msg, sig[:32] + (ref.L).to_bytes(32, "little"))
    assert not ref.verify(pk[:31], msg, sig)


def test_agrees_with_the_programs_host_signer():
    from tendermint_tpu.crypto import ed25519 as ed

    for i in range(4):
        sk = bytes([i + 1]) * 32
        assert ref.public_key(sk) == ed.public_key(sk)
        assert ref.sign(sk, b"m%d" % i) == ed.sign(sk, b"m%d" % i)


def test_kv_reference():
    kv = kv_ref.KVReference()
    kv.apply_payload(b"a=1")
    kv.apply_payload(b"a=2")
    kv.apply_payload(b"bare")
    assert kv.get(b"a") == b"2" and kv.get(b"bare") == b"bare" and kv.get(b"x") == b""
    sk = bytes([9]) * 32
    tx = ref.public_key(sk) + ref.sign(sk, b"k=v") + b"k=v"
    assert kv_ref.tx_valid(tx)
    assert not kv_ref.tx_valid(tx[:40] + bytes([tx[40] ^ 1]) + tx[41:])
    assert not kv_ref.tx_valid(tx[:96])
