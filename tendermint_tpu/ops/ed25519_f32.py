"""Batched Ed25519 verification in fp32 radix-2^8 limbs — the production
TPU kernel.

Replaces the reference's sequential per-signature verify loops
(types/vote_set.go:175, types/validator_set.go:247-250) with a wide SIMD
batch. The field arithmetic runs in float32, where the TPU VPU fuses
multiply+accumulate into FMAs: the schoolbook row sums become FMA chains
instead of the separate int multiply + mask + shift + add sequences of
the int32 radix-2^15 field (ops/ed25519.py, which the aggregate-commit
lanes still use).

EXACTNESS ARGUMENT (all fp32 values are integers; fp32 is exact for
integers < 2^24; every intermediate below stays under 2^23.5):

- Field elements are 32 limbs of radix 2^8, layout (32, B) float32,
  limb-major (batch minor = TPU lane dimension).
- "Loose" limbs after a 3-pass carry satisfy: limb0 <= 749, limbs 1..31
  <= 268 (pass 3 carries are <= 13, and limb0 absorbs 38*carry_top).
- fadd output: inputs <= 825 per limb -> sum <= 1650 -> 1-pass carry
  gives limb0 <= 255+38*6=483, others <= 262.
- fsub(a, b) = carry1(a + PAD - b) where PAD has all limbs in
  [1024, 1279] and value == 0 mod p (see _make_pad), so every limb stays
  non-negative; carry input <= 749+1279 = 2028 -> 1-pass output
  limb0 <= 255+38*7 = 521, others <= 262.
- fmul row sums: with operand limbs bounded as above, anti-diagonal k has
  at most one (0,0) term <= 749^2 = 562k, two limb0 cross terms
  <= 2*749*825 = 1.24M, and 30 generic terms <= 30*825^2 = 20M... the
  825 bound only ever applies to ONE operand (fadd outputs feed fmul
  opposite a table/carry-tight operand in every formula below); the
  worst real pairing is 825-vs-825 in point_double's fsq(fadd(x,y)):
  row sum <= 32*825^2 = 21.8M < 2^24.4 — TOO CLOSE, so point formulas
  pre-carry: fsq/fmul begin with a 1-pass carry when fed by fadd
  (handled by fadd itself carrying to <= 483/262: row sums
  <= 483^2 + 2*483*268 + 30*268^2 = 2.7M < 2^21.4). Products
  <= 749*268 < 2^17.7 each: exact.
- fold (rows k >= 32, weight 2^(8k) = 38*2^(8(k-32)) mod p): each row
  <= 2^21.6 is split hi/lo at 2^8 so the folded addends are <= 38*255
  and 38*2^13.6 = 2^18.9; post-fold rows <= 2^21.7.
- fmul's closing 3-pass carry: pass1 top carry <= 2^13.7 so
  limb0 <= 255 + 38*2^13.7 = 2^19; pass2 limb1 <= 255 + 2^11 = 2303,
  limb0 <= 255 + 38*66 = 2763; pass3 carries <= 13 -> the loose bound
  above. All carry intermediates < 2^21.7: exact.

Verification is strict cofactorless RFC 8032
(compress([s]B + [h](-A)) == R), and the host marshaling is
byte-level (radix-2^8 IS the byte string), which makes prepare cheaper
than the radix-2^15 bit repacking.

Tests cross-check lane-for-lane against crypto/ed25519.py (RFC 8032
vectors, random, malformed).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import ed25519 as ed_ref
from tendermint_tpu.devd_spans import mark

P = ed_ref.P
L = ed_ref.L
NL = 32  # limbs
R = 256.0  # radix
RINV = 1.0 / 256.0


# ---------------------------------------------------------------------------
# constants
# ---------------------------------------------------------------------------


def _int_to_limbs_const(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8).astype(np.float32)


def _make_pad() -> np.ndarray:
    """All-limb pad >= 1024, value == 0 mod p, digits <= 1279: lets fsub
    stay non-negative per limb for any loose operand (limbs <= 749)."""
    base = 1024 * sum(1 << (8 * k) for k in range(NL))
    c = (-base) % P
    digits = np.frombuffer(c.to_bytes(32, "little"), dtype=np.uint8).astype(np.float32)
    pad = digits + 1024.0
    assert (sum(int(pad[k]) << (8 * k) for k in range(NL))) % P == 0
    return pad


_PAD = _make_pad()
_D2 = _int_to_limbs_const((2 * ed_ref.D) % P)
_P_LIMBS = _int_to_limbs_const(P)
_BX = _int_to_limbs_const(ed_ref.B[0])
_BY = _int_to_limbs_const(ed_ref.B[1])


def _affine(pt) -> tuple[int, int]:
    zinv = pow(pt[2], P - 2, P)
    return (pt[0] * zinv % P, pt[1] * zinv % P)


_B2_AFF = _affine(ed_ref.point_add(ed_ref.B, ed_ref.B))
_B3_AFF = _affine(ed_ref.point_add(ed_ref.point_add(ed_ref.B, ed_ref.B), ed_ref.B))
_B2X, _B2Y = _int_to_limbs_const(_B2_AFF[0]), _int_to_limbs_const(_B2_AFF[1])
_B3X, _B3Y = _int_to_limbs_const(_B3_AFF[0]), _int_to_limbs_const(_B3_AFF[1])


# ---------------------------------------------------------------------------
# field arithmetic on (32, B) float32
# ---------------------------------------------------------------------------


def _roll38(hi: jax.Array) -> jax.Array:
    """Carries shift up one limb; the top carry wraps to limb 0 with
    weight 38 (2^256 = 2*19 mod p)."""
    return jnp.concatenate([38.0 * hi[NL - 1 :], hi[: NL - 1]], axis=0)


def _carry1(x: jax.Array) -> jax.Array:
    hi = jnp.floor(x * RINV)
    return x - hi * R + _roll38(hi)


def _carry3(x: jax.Array) -> jax.Array:
    return _carry1(_carry1(_carry1(x)))


def fadd(a: jax.Array, b: jax.Array) -> jax.Array:
    return _carry1(a + b)


def fsub(a: jax.Array, b: jax.Array) -> jax.Array:
    return _carry1(a + jnp.asarray(_PAD)[:, None] - b)


def fmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Schoolbook limb multiply as a depthwise 1-D convolution: the
    anti-diagonal row sums c_k = sum_i a_i*b_{k-i} ARE a length-32 full
    correlation per lane, which XLA lowers onto the MXU (batch = conv
    channels, limbs = spatial). Measured 13us vs 44us for the int32
    rank-1-update formulation at B=8192 — and ~15 HLO ops instead of ~90,
    so the full ladder graph compiles quickly.

    Precision=HIGHEST makes the MXU passes exact for the integer ranges
    here (products < 2^21, row sums < 2^23.5; verified against python
    ints with limbs pinned at the loose-bound maxima)."""
    batch = a.shape[-1]
    lhs = a.T[None]  # (1, B, 32)  N=1, C=batch, W=limbs
    rhs = b.T[:, None, ::-1]  # (B, 1, 32) depthwise filters (reversed)
    out = jax.lax.conv_general_dilated(
        lhs,
        rhs,
        window_strides=(1,),
        padding=[(NL - 1, NL - 1)],
        feature_group_count=batch,
        dimension_numbers=("NCW", "OIW", "NCW"),
        precision=jax.lax.Precision.HIGHEST,
    )
    rows = out[0].T  # (63, B): rows[k] = sum_{i+j=k} a_i * b_j
    # fold rows k>=32 (weight 2^(8k) = 38*2^(8(k-32)) mod p) with a hi/lo
    # split so every addend stays well under 2^24
    t = rows[NL:]
    t_hi = jnp.floor(t * RINV)
    t_lo = t - t_hi * R
    res = rows[:NL]
    res = res.at[: NL - 1].add(38.0 * t_lo)
    res = res.at[1:NL].add(38.0 * t_hi)
    return _carry3(res)


def fsq(a: jax.Array) -> jax.Array:
    return fmul(a, a)


def _rep_sq(x: jax.Array, n: int) -> jax.Array:
    if n <= 8:
        for _ in range(n):
            x = fsq(x)
        return x
    return jax.lax.fori_loop(0, n, lambda _, v: fsq(v), x)


def finv(z: jax.Array) -> jax.Array:
    z2 = fsq(z)
    z9 = fmul(_rep_sq(z2, 2), z)
    z11 = fmul(z9, z2)
    z_5_0 = fmul(fsq(z11), z9)
    z_10_0 = fmul(_rep_sq(z_5_0, 5), z_5_0)
    z_20_0 = fmul(_rep_sq(z_10_0, 10), z_10_0)
    z_40_0 = fmul(_rep_sq(z_20_0, 20), z_20_0)
    z_50_0 = fmul(_rep_sq(z_40_0, 10), z_10_0)
    z_100_0 = fmul(_rep_sq(z_50_0, 50), z_50_0)
    z_200_0 = fmul(_rep_sq(z_100_0, 100), z_100_0)
    z_250_0 = fmul(_rep_sq(z_200_0, 50), z_50_0)
    return fmul(_rep_sq(z_250_0, 5), z11)


def _seq_carry(x: jax.Array) -> jax.Array:
    """One sequential full carry pass limb 0 -> 31; the carry out of the
    top limb wraps to limb 0 with weight 38. Unlike the parallel _carry1
    (which leaves each limb's incoming carry un-propagated), this
    guarantees limbs 1..31 end in [0, 256); limb 0 may exceed 255 only by
    the wrapped 38*carry_top."""
    carry = jnp.zeros(x.shape[-1], dtype=jnp.float32)
    out = []
    for k in range(NL):
        v = x[k] + carry
        carry = jnp.floor(v * RINV)
        out.append(v - carry * R)
    res = jnp.stack(out, axis=0)
    return res.at[0].add(38.0 * carry)


def fcanon(x: jax.Array) -> jax.Array:
    """Fully reduce to canonical digits in [0, 256) representing a value
    in [0, p).

    Three sequential carry passes provably canonicalize any loose input
    (limbs <= 825): pass 1 carries are <= 3 so limb0 <= 255 + 38*3 = 369
    with all other digits < 256; pass 2's top carry is then <= 1 so
    limb0 <= 293; if pass 3 still wraps, the pre-wrap value was
    < 2^256 + 76, so the post-wrap value is < 76 + 38 — canonical either
    way. (A parallel-only carry chain is NOT enough: carries landing on
    limb 0 can leave it at up to 293 for values < p, and the digit-wise
    equality check in _verify_impl would then falsely reject a valid
    signature — found by round-2 review, regression-tested in
    tests/test_ops_f32.py.) Then <= 2 conditional subtractions of p
    bring the value below p (2^256 < 3p)."""
    x = _seq_carry(_seq_carry(_seq_carry(x)))
    for _ in range(2):
        borrow = None
        out = []
        for k in range(NL):
            v = x[k] - float(_P_LIMBS[k]) - (borrow if borrow is not None else 0.0)
            neg = (v < 0).astype(jnp.float32)
            out.append(v + neg * R)
            borrow = neg
        sub = jnp.stack(out, axis=0)
        ge = borrow == 0
        x = jnp.where(ge[None, :], sub, x)
    return x


# ---------------------------------------------------------------------------
# point arithmetic (extended coordinates), complete formulas
# ---------------------------------------------------------------------------


def point_add(p1, p2, d2):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = fmul(fsub(y1, x1), fsub(y2, x2))
    b = fmul(fadd(y1, x1), fadd(y2, x2))
    c = fmul(fmul(t1, t2), d2)
    zz = fmul(z1, z2)
    d = fadd(zz, zz)
    e = fsub(b, a)
    f = fsub(d, c)
    g = fadd(d, c)
    h = fadd(b, a)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


def point_double(p1):
    x1, y1, z1, _ = p1
    a = fsq(x1)
    b = fsq(y1)
    zz = fsq(z1)
    c = fadd(zz, zz)
    h = fadd(a, b)
    e = fsub(h, fsq(fadd(x1, y1)))
    g = fsub(a, b)
    f = fadd(c, g)
    return (fmul(e, f), fmul(g, h), fmul(f, g), fmul(e, h))


# ---------------------------------------------------------------------------
# the verify kernel
# ---------------------------------------------------------------------------


def _digits2(limbs_u8: jax.Array) -> jax.Array:
    """(32,B) int32 byte limbs -> (127,B) int32 2-bit digits MSB-first.
    Scalars < L < 2^253, so digits above 126 are zero."""
    shifts = jnp.arange(0, 8, 2, dtype=jnp.int32)  # bit pairs within a byte
    d = (limbs_u8[:, None, :] >> shifts[None, :, None]) & 3  # (32,4,B)
    d = d.reshape(NL * 4, limbs_u8.shape[-1])[:127]  # little-endian digits
    return d[::-1]


def _verify_impl(ax, ay, r_y, r_sign, s8, h8):
    """ax/ay: affine pubkey limbs (32,B) f32; r_y: R's y limbs (canonical);
    r_sign: (B,) int32 x-parity of R; s8/h8: (32,B) int32 byte limbs of the
    scalars. Returns bool[B].

    Interleaved Straus, 2-bit joint windows: 127 x (2 doublings + 1
    16-entry table add)."""
    batch = ax.shape[-1]
    # derive from the input (not jnp.zeros): the scan carry must be
    # batch-varying from step 0 under shard_map's manual axes (see the
    # same construction in ed25519_f32p._ladder); value-identical
    zeros = ax * 0.0
    one = zeros.at[0].set(1.0)
    d2 = jnp.broadcast_to(jnp.asarray(_D2)[:, None], (NL, batch))

    def const_pt(xc, yc):
        x = jnp.broadcast_to(jnp.asarray(xc)[:, None], (NL, batch))
        y = jnp.broadcast_to(jnp.asarray(yc)[:, None], (NL, batch))
        return (x, y, one, fmul(x, y))

    nax = fsub(zeros, ax)
    neg_a = (nax, ay, one, fmul(nax, ay))
    na2 = point_double(neg_a)
    na3 = point_add(na2, neg_a, d2)
    ident = (zeros, one, one, zeros)
    b_row = [ident, const_pt(_BX, _BY), const_pt(_B2X, _B2Y), const_pt(_B3X, _B3Y)]
    a_row = [ident, neg_a, na2, na3]
    table = []
    for j in range(4):
        for i in range(4):
            if i == 0:
                table.append(a_row[j])
            elif j == 0:
                table.append(b_row[i])
            else:
                table.append(point_add(b_row[i], a_row[j], d2))
    tcoords = [jnp.stack([t[c] for t in table], axis=0) for c in range(4)]  # (16,32,B)

    xs = jnp.stack([_digits2(s8), _digits2(h8)], axis=1)  # (127,2,B)
    idx16 = jnp.arange(16, dtype=jnp.int32)

    def step(acc, dig):
        acc = point_double(point_double(acc))
        sel = dig[0] + 4 * dig[1]  # (B,)
        onehot = (sel[None, :] == idx16[:, None]).astype(jnp.float32)  # (16,B)
        addend = tuple(jnp.sum(onehot[:, None, :] * tc, axis=0) for tc in tcoords)
        return point_add(acc, addend, d2), None

    acc, _ = jax.lax.scan(step, ident, xs)

    px, py, pz, _ = acc
    zinv = finv(pz)
    x_aff = fcanon(fmul(px, zinv))
    y_aff = fcanon(fmul(py, zinv))
    sign = x_aff[0].astype(jnp.int32) & 1
    return jnp.all(y_aff == fcanon(r_y), axis=0) & (sign == r_sign)


_verify_jit = jax.jit(_verify_impl)


# ---------------------------------------------------------------------------
# host marshaling: byte-level (radix-2^8 IS the little-endian byte string)
#
# This is the sustained-throughput bottleneck the kernel exposes: at
# batch 8192 the device runs ~91 ms while a per-item python loop
# (sha512 + decompress each) took ~146 ms, capping the delivered rate at
# half the kernel's. The marshal below is vectorized numpy for the
# canonical checks, one native C call per batch for the SHA512(R||A||M)
# mod L digests (tm_ed25519_hram_batch), and native batch decompression
# of only the UNIQUE pubkeys (validator keys repeat every commit) with a
# host-side cache. Pure-python fallbacks cover a missing native library.
# ---------------------------------------------------------------------------

_pubkey_cache: dict[bytes, tuple[bytes, bytes] | None] = {}

_L_ARR = np.frombuffer(L.to_bytes(32, "little"), dtype=np.uint8)
_P_ARR = np.frombuffer(P.to_bytes(32, "little"), dtype=np.uint8)
_Z32 = b"\x00" * 32
_Z64 = b"\x00" * 64


def _lt_bytes_le(a: np.ndarray, c: np.ndarray) -> np.ndarray:
    """value(a[i]) < value(c) for little-endian byte rows a (n,32) vs a
    constant c (32,) — vectorized big-endian lexicographic compare."""
    diff = a != c[None, :]
    first = diff[:, ::-1].argmax(axis=1)  # offset of most-significant diff
    idx = 31 - first
    less = a[np.arange(len(a)), idx] < c[idx]
    return diff.any(axis=1) & less


def _decompress_rows(pub_parts: list[bytes]):
    """n compressed keys -> ((n,32) x, (n,32) y, ok mask), deduplicating
    repeated keys (a commit is few validators, many messages) through the
    host cache, with native batch decompress for the misses."""
    from tendermint_tpu import native

    uniq_index: dict[bytes, int] = {}
    inv = np.empty(len(pub_parts), dtype=np.intp)
    uniq: list[bytes] = []
    for i, key in enumerate(pub_parts):
        j = uniq_index.get(key)
        if j is None:
            j = len(uniq)
            uniq_index[key] = j
            uniq.append(key)
        inv[i] = j
    u = len(uniq)
    ux = np.zeros((u, 32), dtype=np.uint8)
    uy = np.zeros((u, 32), dtype=np.uint8)
    uok = np.zeros(u, dtype=bool)
    misses = []
    for j, key in enumerate(uniq):
        hit = _pubkey_cache.get(key, False)
        if hit is False:
            misses.append(j)
        elif hit is not None:
            ux[j] = np.frombuffer(hit[0], dtype=np.uint8)
            uy[j] = np.frombuffer(hit[1], dtype=np.uint8)
            uok[j] = True
    if misses:
        if native.available():
            flat = np.frombuffer(
                b"".join(uniq[j] for j in misses), dtype=np.uint8
            )
            xy, ok = native.ed25519_decompress_batch(
                np.ascontiguousarray(flat), len(misses)
            )
            midx = np.asarray(misses)
            ux[midx] = xy[:, :32]
            uy[midx] = xy[:, 32:]
            uok[midx] = ok
            for k, j in enumerate(misses):
                if len(_pubkey_cache) < 1_000_000:
                    _pubkey_cache[uniq[j]] = (
                        (xy[k, :32].tobytes(), xy[k, 32:].tobytes())
                        if ok[k]
                        else None
                    )
        else:
            for j in misses:
                key = uniq[j]
                pt = ed_ref.point_decompress(key)
                res = None if pt is None else (
                    pt[0].to_bytes(32, "little"),
                    pt[1].to_bytes(32, "little"),
                )
                if len(_pubkey_cache) < 1_000_000:
                    _pubkey_cache[key] = res
                if res is not None:
                    ux[j] = np.frombuffer(res[0], dtype=np.uint8)
                    uy[j] = np.frombuffer(res[1], dtype=np.uint8)
                    uok[j] = True
    return ux[inv], uy[inv], uok[inv]


def _hram_rows(
    sigs: np.ndarray, pubs: np.ndarray, msgs: list[bytes], valid: np.ndarray
) -> np.ndarray:
    """(n,32) u8 LE rows of SHA512(R || A || M) mod L."""
    from tendermint_tpu import native

    n = len(msgs)
    if native.available():
        offsets = np.zeros(n + 1, dtype=np.uint64)
        total = 0
        for i, m in enumerate(msgs):
            total += len(m)
            offsets[i + 1] = total
        data = (
            np.frombuffer(b"".join(msgs), dtype=np.uint8)
            if total
            else np.zeros(1, np.uint8)
        )
        return native.ed25519_hram_batch(
            np.ascontiguousarray(sigs).reshape(-1),
            np.ascontiguousarray(pubs).reshape(-1),
            np.ascontiguousarray(data),
            offsets,
            n,
        )
    h8 = np.zeros((n, 32), dtype=np.uint8)
    for i in range(n):
        if not valid[i]:
            continue
        h = (
            int.from_bytes(
                hashlib.sha512(
                    sigs[i, :32].tobytes() + pubs[i].tobytes() + msgs[i]
                ).digest(),
                "little",
            )
            % L
        )
        h8[i] = np.frombuffer(h.to_bytes(32, "little"), dtype=np.uint8)
    return h8


def prepare_batch8(items: list[tuple[bytes, bytes, bytes]], bucket: int):
    """Marshal (pubkey, msg, sig) triples into kernel inputs.

    Returns (ax f32(32,B), ay f32(32,B), ry f32(32,B), r_sign int32(B,),
    s8 int32(32,B), h8 int32(32,B), valid bool(B,)). Invalid rows (bad
    point/non-canonical s or R/bad lengths) get benign placeholders and
    valid=False. The only per-item python is the shape check + bytes
    collection; checks/digests/decompression are vectorized or native."""
    n = len(items)
    pub_parts: list[bytes] = []
    sig_parts: list[bytes] = []
    msgs: list[bytes] = []
    shape_ok = np.ones(n, dtype=bool)
    for i, (pub, msg, sig) in enumerate(items):
        if len(sig) != 64 or len(pub) != 32:
            shape_ok[i] = False
            pub_parts.append(_Z32)
            sig_parts.append(_Z64)
            msgs.append(b"")
        else:
            pub_parts.append(bytes(pub))
            sig_parts.append(bytes(sig))
            msgs.append(bytes(msg))

    pubs = (
        np.frombuffer(b"".join(pub_parts), dtype=np.uint8).reshape(n, 32)
        if n
        else np.zeros((0, 32), dtype=np.uint8)
    )
    sigs = (
        np.frombuffer(b"".join(sig_parts), dtype=np.uint8).reshape(n, 64)
        if n
        else np.zeros((0, 64), dtype=np.uint8)
    )
    s_rows = sigs[:, 32:]
    r_rows = sigs[:, :32].copy()
    top = r_rows[:, 31].copy()
    r_rows[:, 31] &= 0x7F
    rs_rows = (top >> 7).astype(np.int32)

    s_ok = _lt_bytes_le(s_rows, _L_ARR)  # s < L
    r_ok = _lt_bytes_le(r_rows, _P_ARR)  # canonical R.y < p
    ax_rows, ay_rows, a_ok = _decompress_rows(pub_parts)
    valid_n = shape_ok & s_ok & r_ok & a_ok
    h_rows = _hram_rows(sigs, pubs, msgs, valid_n)

    # benign placeholders on invalid rows (and bucket padding): the kernel
    # runs every lane, so inputs must stay byte-valued; results are masked.
    inval = ~valid_n
    ax = np.zeros((bucket, 32), dtype=np.uint8)
    ay = np.zeros((bucket, 32), dtype=np.uint8)
    ay[:, 0] = 1
    ry = np.zeros((bucket, 32), dtype=np.uint8)
    ry[:, 0] = 1
    rs = np.zeros(bucket, dtype=np.int32)
    s8 = np.zeros((bucket, 32), dtype=np.uint8)
    h8 = np.zeros((bucket, 32), dtype=np.uint8)
    valid = np.zeros(bucket, dtype=bool)
    if n:
        ax[:n] = np.where(inval[:, None], 0, ax_rows)
        ay[:n] = np.where(inval[:, None], ay[:n], ay_rows)
        ry[:n] = np.where(inval[:, None], ry[:n], r_rows)
        rs[:n] = np.where(inval, 0, rs_rows)
        s8[:n] = np.where(inval[:, None], 0, s_rows)
        h8[:n] = np.where(inval[:, None], 0, h_rows)
        valid[:n] = valid_n

    return (
        np.ascontiguousarray(ax.T.astype(np.float32)),
        np.ascontiguousarray(ay.T.astype(np.float32)),
        np.ascontiguousarray(ry.T.astype(np.float32)),
        rs,
        np.ascontiguousarray(s8.T.astype(np.int32)),
        np.ascontiguousarray(h8.T.astype(np.int32)),
        valid,
    )


def _next_pow2(n: int) -> int:
    b = 8
    while b < n:
        b <<= 1
    return b


def verify_batch_async(items: list[tuple[bytes, bytes, bytes]]):
    """Marshal + enqueue the device kernel now; return a zero-arg resolver
    that materializes bool[B]. The single definition of the marshal/
    dispatch/mask sequence — verify_batch is this plus an immediate
    resolve, so the sync and async paths cannot drift."""
    n = len(items)
    if n == 0:
        return lambda: np.zeros(0, dtype=bool)
    bucket = _next_pow2(n)
    ax, ay, ry, rs, s8, h8, valid = prepare_batch8(items, bucket)
    # the daemon's per-call record (devd_spans): one attribute test each
    # where no record is open, which is everywhere but inside devd
    mark("marshal", bucket)
    ok_dev = _verify_jit(
        jnp.asarray(ax),
        jnp.asarray(ay),
        jnp.asarray(ry),
        jnp.asarray(rs),
        jnp.asarray(s8),
        jnp.asarray(h8),
    )
    mark("dispatch")

    def resolve():
        ok = np.asarray(ok_dev)
        mark("device_wait")
        return ok[:n] & valid[:n]

    return resolve


def verify_batch(items: list[tuple[bytes, bytes, bytes]]) -> np.ndarray:
    """Batched strict-RFC8032 verify -> bool[B]; semantics identical to
    crypto.ed25519.verify per item. Padded to power-of-two buckets so jit
    recompilation is bounded."""
    return verify_batch_async(items)()
