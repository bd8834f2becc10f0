"""GF(2^255-19) field and Edwards-curve arithmetic on int32 lanes, and the
batched dual scalar multiplication built on it (pure jnp).

What this module serves: `dsm_batch`, the per-lane [a]P + [b]Q over
VARIABLE points that the aggregate-commit verify needs
(crypto/ed25519_agg.py, docs/upgrade.md; called by ops/gateway.py
Verifier.verify_aggregate in process and by the device daemon's `agg`
op). Signature verification is not here: the verify kernels are
ops/ed25519_comb.py, ops/ed25519_f32p.py and ops/ed25519_f32.py
(ops/gateway.py KERNELS).

Design notes (TPU-first, not a port of any CPU bignum library):

- Field GF(2^255-19) in radix 2^15 with 17 limbs (15*17 = 255, so the
  modular fold is limb-aligned: limb k >= 17 folds into limb k-17 times 19).
- LIMB-MAJOR layout: a batch of field elements is int32[17, B] — the batch
  axis is the TPU's 128-wide lane dimension, the limb axis is the
  instruction stream. Every limb operation is a full-width vector op; with
  the batch axis minor there are strided column accesses and wasted lanes.
- 15-bit limbs keep every partial product under 2^30; products are split
  hi/lo at bit 15 BEFORE accumulation so row sums stay under 2^21 — the
  whole multiply needs no 64-bit type (TPU has no native wide int).
  Anti-diagonal accumulation uses shift-and-add, not scatter.
- The scalar multiplication is interleaved Straus with 2-bit joint
  windows under lax.scan: per step two complete-Edwards doublings and one
  add from a 16-entry table. Complete formulas (RFC 8032 section 5.1.4)
  mean no data-dependent branches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import ed25519 as ed_ref

P = ed_ref.P
M15 = 0x7FFF
NLIMB = 17

# ---------------------------------------------------------------------------
# host <-> limb conversion (host arrays are (B, 17); device layout (17, B))
# ---------------------------------------------------------------------------


def int_to_limbs_np(vals: list[int]) -> np.ndarray:
    """list of ints < 2^256 -> int32[17, B] radix-2^15 limb-major limbs."""
    b = np.zeros((len(vals), 32), dtype=np.uint8)
    for i, v in enumerate(vals):
        b[i] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
    bits = np.unpackbits(b, axis=1, bitorder="little")  # (B, 256)
    limbs = bits[:, :255].reshape(len(vals), NLIMB, 15)
    weights = (1 << np.arange(15)).astype(np.int32)
    return np.ascontiguousarray((limbs * weights).sum(axis=2).astype(np.int32).T)


def limbs_to_int(limbs: np.ndarray) -> int:
    """int32[17] -> int."""
    return sum(int(limbs[k]) << (15 * k) for k in range(NLIMB))


def _const_limbs(v: int) -> np.ndarray:
    return int_to_limbs_np([v])[:, 0]  # (17,)


_D2 = _const_limbs((2 * ed_ref.D) % P)
_P_LIMBS = np.array([32749] + [32767] * 16, dtype=np.int32)
_PX2 = (2 * _P_LIMBS).astype(np.int32)


# ---------------------------------------------------------------------------
# field arithmetic on (17, B) int32 arrays
# ---------------------------------------------------------------------------


def _roll19(hi: jax.Array) -> jax.Array:
    """Shift carries up one limb; the top limb's carry wraps to limb 0
    with weight 19 (2^255 = 19 mod p)."""
    return jnp.concatenate([19 * hi[NLIMB - 1 :], hi[: NLIMB - 1]], axis=0)


def _carry(x: jax.Array) -> jax.Array:
    """Reduce limbs to the LOOSE range [0, ~2^15]; inputs non-negative
    < 2^26 per limb. TWO fully-parallel passes instead of a 17-step
    sequential chain — the chain was the kernel's critical path (every
    fmul ends in a carry; the ladder runs ~4000 of them).

    Bounds: pass 1 carries < 2^11 (19x top-fold < 19*2^11), so y < 2^15 +
    19*2^11 < 2^17; pass 2 carries <= 3, leaving limbs <= 2^15 - 1 + 57.
    The multiply tolerates that loose bound: products stay < 2^31 and the
    17-row accumulator sums < 2^21 per window, refolding < 2^26 — inside
    this function's own input bound, so the loose form is closed under
    fmul/fadd/fsub."""
    y = (x & M15) + _roll19(x >> 15)
    return (y & M15) + _roll19(y >> 15)


def fadd(a: jax.Array, b: jax.Array) -> jax.Array:
    return _carry(a + b)


def fsub(a: jax.Array, b: jax.Array) -> jax.Array:
    return _carry(a + jnp.asarray(_PX2)[:, None] - b)


def fmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Schoolbook multiply, hi/lo split, shift-and-add accumulation.
    a, b: (17, B) -> (17, B). All int32, batch-width vector ops only."""
    # 17 rank-1 row updates: row i of the schoolbook grid is a[i] * b —
    # ONE (17,B) multiply — whose hi/lo halves land at limb windows
    # [i, i+17) and [i+1, i+18) of a 35-limb accumulator via static slice
    # adds. ~90 medium-sized HLO ops per multiply: small enough for XLA to
    # compile quickly, dataflow-only so it fuses with VMEM-resident
    # intermediates (the fully-unrolled 900-op variant compiled for >10min;
    # the batch-minor variant wasted 7/8 of the VPU lanes).
    batch = a.shape[-1]
    acc = jnp.zeros((34, batch), dtype=jnp.int32)
    for i in range(NLIMB):
        p = a[i][None, :] * b  # (17, B) < 2^30
        acc = acc.at[i : i + NLIMB].add(p & M15)
        acc = acc.at[i + 1 : i + 1 + NLIMB].add(p >> 15)
    # fold: limb k>=17 has weight 2^(15k) = 19 * 2^(15(k-17)); the hi
    # window of row 16 tops out at limb 33, so one fold suffices
    res = acc[:NLIMB] + 19 * acc[NLIMB:34]
    return _carry(res)


def fsq(a: jax.Array) -> jax.Array:
    return fmul(a, a)


def _rep_sq(x: jax.Array, n: int) -> jax.Array:
    """n repeated squarings; rolled into fori_loop past a small count to
    keep the HLO graph (and compile time) bounded."""
    if n <= 8:
        for _ in range(n):
            x = fsq(x)
        return x
    return jax.lax.fori_loop(0, n, lambda _, v: fsq(v), x)


def finv(z: jax.Array) -> jax.Array:
    """z^(p-2) via the standard 254-squaring addition chain."""
    z2 = fsq(z)
    z9 = fmul(_rep_sq(z2, 2), z)
    z11 = fmul(z9, z2)
    z_5_0 = fmul(fsq(z11), z9)  # 2^5 - 1
    z_10_0 = fmul(_rep_sq(z_5_0, 5), z_5_0)
    z_20_0 = fmul(_rep_sq(z_10_0, 10), z_10_0)
    z_40_0 = fmul(_rep_sq(z_20_0, 20), z_20_0)
    z_50_0 = fmul(_rep_sq(z_40_0, 10), z_10_0)
    z_100_0 = fmul(_rep_sq(z_50_0, 50), z_50_0)
    z_200_0 = fmul(_rep_sq(z_100_0, 100), z_100_0)
    z_250_0 = fmul(_rep_sq(z_200_0, 50), z_50_0)
    return fmul(_rep_sq(z_250_0, 5), z11)  # 2^255 - 21


def fcanon(x: jax.Array) -> jax.Array:
    """Fully reduce to the canonical representative in [0, p)."""
    x = _carry(x)
    for _ in range(2):
        borrow = None
        out = []
        for k in range(NLIMB):
            v = x[k] - int(_P_LIMBS[k]) - (borrow if borrow is not None else 0)
            out.append(v & M15)
            borrow = (v >> 15) & 1
        sub = jnp.stack(out, axis=0)
        ge = borrow == 0
        x = jnp.where(ge[None, :], sub, x)
    return x


# ---------------------------------------------------------------------------
# point arithmetic (extended coordinates X, Y, Z, T), complete formulas
# ---------------------------------------------------------------------------


def point_add(p1, p2):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = fmul(fsub(y1, x1), fsub(y2, x2))
    b = fmul(fadd(y1, x1), fadd(y2, x2))
    c = fmul(fmul(t1, t2), jnp.asarray(_D2)[:, None])
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


def _identity(batch: int):
    zeros = jnp.zeros((NLIMB, batch), dtype=jnp.int32)
    one = zeros.at[0].set(1)
    return (zeros, one, one, zeros)


# ---------------------------------------------------------------------------
# scalar digits and batch padding
# ---------------------------------------------------------------------------


def _digits2_from_limbs(limbs: jax.Array) -> jax.Array:
    """(17,B) 15-bit limbs -> (127,B) 2-bit digits, MSB-first. Scalars are
    < L < 2^253, so bits 253/254 are zero. Unpacking on-device keeps the
    host->device transfer at 17 words/scalar instead of 253 bit-ints."""
    shifts = jnp.arange(15, dtype=jnp.int32)
    bits = (limbs[:, None, :] >> shifts[None, :, None]) & 1  # (17,15,B)
    bits = bits.reshape(NLIMB * 15, limbs.shape[-1])[:254]  # little-endian
    d = bits[0::2] + 2 * bits[1::2]  # (127,B)
    return d[::-1]


def _next_pow2(n: int) -> int:
    b = 8
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# batched dual scalar multiplication: per-lane [a]P + [b]Q for VARIABLE
# points (the aggregate-commit verify's per-lane term [z_i]R_i +
# [z_i*h_i]A_i — see crypto/ed25519_agg.py and docs/upgrade.md). A 2-bit
# interleaved Straus scan whose whole 16-entry table is built from
# per-lane points.
# ---------------------------------------------------------------------------


def _dsm_impl(px, py, qx, qy, a_limbs, b_limbs):
    """px/py, qx/qy: affine point limbs (17,B); a_limbs/b_limbs: (17,B)
    15-bit limb scalars (< L). Returns canonical affine (x (17,B),
    y (17,B)) of [a]P + [b]Q per lane."""
    batch = px.shape[-1]
    zeros = jnp.zeros((NLIMB, batch), dtype=jnp.int32)
    one = zeros.at[0].set(1)

    p1 = (px, py, one, fmul(px, py))
    q1 = (qx, qy, one, fmul(qx, qy))
    p2, q2 = point_double(p1), point_double(q1)
    p3, q3 = point_add(p2, p1), point_add(q2, q1)
    ident = _identity(batch)
    p_row = [ident, p1, p2, p3]
    q_row = [ident, q1, q2, q3]
    table = []
    for j in range(4):  # b digit (multiples of Q)
        for i in range(4):  # a digit (multiples of P)
            if i == 0:
                table.append(q_row[j])
            elif j == 0:
                table.append(p_row[i])
            else:
                table.append(point_add(p_row[i], q_row[j]))
    tcoords = [jnp.stack([t[c] for t in table], axis=0) for c in range(4)]

    xs = jnp.stack(
        [_digits2_from_limbs(a_limbs), _digits2_from_limbs(b_limbs)], axis=1
    )  # (127,2,B)
    idx16 = jnp.arange(16, dtype=jnp.int32)

    def step(acc, dig):
        acc = point_double(point_double(acc))
        sel = dig[0] + 4 * dig[1]
        onehot = (sel[None, :] == idx16[:, None]).astype(jnp.int32)
        addend = tuple(
            jnp.sum(onehot[:, None, :] * tc, axis=0) for tc in tcoords
        )
        return point_add(acc, addend), None

    acc, _ = jax.lax.scan(step, ident, xs)
    ax_, ay_, az_, _ = acc
    zinv = finv(az_)
    return fcanon(fmul(ax_, zinv)), fcanon(fmul(ay_, zinv))


_dsm_jit = jax.jit(_dsm_impl)

# identity lane padding for dsm_batch: [0]P + [0]Q from the neutral point
_DSM_PAD = (0, (0, 1), 0, (0, 1))


def dsm_batch(
    terms: list[tuple[int, tuple[int, int], int, tuple[int, int]]],
) -> list[tuple[int, int]]:
    """terms: (a, (px, py), b, (qx, qy)) per lane, scalars already
    reduced mod L, points affine on-curve (caller-validated — the
    aggregate path decompresses via crypto/ed25519.point_decompress).
    Returns per-lane affine [a]P + [b]Q as python ints. Padded to the
    next power of two (one compiled program per bucket)."""
    n = len(terms)
    if n == 0:
        return []
    bucket = _next_pow2(n)
    padded = list(terms) + [_DSM_PAD] * (bucket - n)
    a_i = [t[0] for t in padded]
    b_i = [t[2] for t in padded]
    px_i = [t[1][0] for t in padded]
    py_i = [t[1][1] for t in padded]
    qx_i = [t[3][0] for t in padded]
    qy_i = [t[3][1] for t in padded]
    x_l, y_l = _dsm_jit(
        jnp.asarray(int_to_limbs_np(px_i)),
        jnp.asarray(int_to_limbs_np(py_i)),
        jnp.asarray(int_to_limbs_np(qx_i)),
        jnp.asarray(int_to_limbs_np(qy_i)),
        jnp.asarray(int_to_limbs_np(a_i)),
        jnp.asarray(int_to_limbs_np(b_i)),
    )
    x_np, y_np = np.asarray(x_l), np.asarray(y_l)
    return [
        (limbs_to_int(x_np[:, i]), limbs_to_int(y_np[:, i])) for i in range(n)
    ]


