"""Pallas TPU kernel for batched Ed25519 verification.

STATUS: bake-off alternative, selectable with TENDERMINT_TPU_KERNEL=pallas.
Lost the production bake-off to ops/ed25519_f32.py (32.6k vs 94.4k sigs/s
at batch 8192 on a v5e — see ops/gateway.py KERNELS): the f32 kernel's
conv-lowered field multiplies ride the MXU while this ladder is VPU-bound
int32 work, and VMEM residency alone doesn't close that gap. Kept as the
VMEM-resident reference point for future pallas work and as a second
device implementation the tests cross-check.

The XLA-composed variant (ops/ed25519.py) bottoms out at ~350ms/batch on a
v5e because the limb accumulator updates materialize through HBM between
HLO ops. This kernel runs the ENTIRE double-scalar ladder inside one
pallas_call: field elements live as (1, TB)-row register/VMEM values for a
lane tile of TB signatures, the 253-iteration Straus loop is a fori_loop,
and nothing touches HBM between bit steps.

Same math as ops/ed25519.py (radix-2^15/17-limb int32, hi/lo split,
complete Edwards formulas, compress-and-compare against R); the host
marshaling (prepare_batch) is shared. Tests cross-check lane-for-lane
against the CPU verifier.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops import ed25519 as base

NLIMB = base.NLIMB
M15 = base.M15

# Field elements inside the kernel are Python lists of 17 (1, TB) int32
# arrays — fully unrolled limb arithmetic on full-width vector rows.


def _carry_rows(x: list):
    out = []
    c = None
    for k in range(NLIMB):
        v = x[k] if c is None else x[k] + c
        out.append(v & M15)
        c = v >> 15
    v0 = out[0] + 19 * c
    out[0] = v0 & M15
    out[1] = out[1] + (v0 >> 15)
    return out


def _fmul_rows(a: list, b: list) -> list:
    acc = [None] * 34
    for i in range(NLIMB):
        ai = a[i]
        for j in range(NLIMB):
            p = ai * b[j]
            lo = p & M15
            hi = p >> 15
            k = i + j
            acc[k] = lo if acc[k] is None else acc[k] + lo
            acc[k + 1] = hi if acc[k + 1] is None else acc[k + 1] + hi
    res = [acc[k] for k in range(NLIMB)]
    for k in range(NLIMB, 34):
        res[k - NLIMB] = res[k - NLIMB] + 19 * acc[k]
    return _carry_rows(res)


def _fsq_rows(a: list) -> list:
    acc = [None] * 34
    for i in range(NLIMB):
        p = a[i] * a[i]
        lo, hi = p & M15, p >> 15
        k = 2 * i
        acc[k] = lo if acc[k] is None else acc[k] + lo
        acc[k + 1] = hi if acc[k + 1] is None else acc[k + 1] + hi
        for j in range(i + 1, NLIMB):
            p2 = 2 * (a[i] * a[j])
            lo, hi = p2 & M15, p2 >> 15
            k = i + j
            acc[k] = lo if acc[k] is None else acc[k] + lo
            acc[k + 1] = hi if acc[k + 1] is None else acc[k + 1] + hi
    res = [acc[k] for k in range(NLIMB)]
    for k in range(NLIMB, 34):
        res[k - NLIMB] = res[k - NLIMB] + 19 * acc[k]
    return _carry_rows(res)


_PX2_L = [int(v) for v in base._PX2]
_P_L = [int(v) for v in base._P_LIMBS]
_D2_L = [int(v) for v in base._D2]
_BX_L = [int(v) for v in base._BX]
_BY_L = [int(v) for v in base._BY]
_BT_L = [int(v) for v in base._BT]


def _fadd_rows(a, b):
    return _carry_rows([a[k] + b[k] for k in range(NLIMB)])


def _fsub_rows(a, b):
    return _carry_rows([a[k] + _PX2_L[k] - b[k] for k in range(NLIMB)])


def _point_add_rows(p1, p2, d2_rows):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = _fmul_rows(_fsub_rows(y1, x1), _fsub_rows(y2, x2))
    b = _fmul_rows(_fadd_rows(y1, x1), _fadd_rows(y2, x2))
    c = _fmul_rows(_fmul_rows(t1, t2), d2_rows)
    zz = _fmul_rows(z1, z2)
    d = _fadd_rows(zz, zz)
    e = _fsub_rows(b, a)
    f = _fsub_rows(d, c)
    g = _fadd_rows(d, c)
    h = _fadd_rows(b, a)
    return (
        _fmul_rows(e, f),
        _fmul_rows(g, h),
        _fmul_rows(f, g),
        _fmul_rows(e, h),
    )


def _point_double_rows(p1):
    x1, y1, z1, _ = p1
    a = _fsq_rows(x1)
    b = _fsq_rows(y1)
    zz = _fsq_rows(z1)
    c = _fadd_rows(zz, zz)
    h = _fadd_rows(a, b)
    e = _fsub_rows(h, _fsq_rows(_fadd_rows(x1, y1)))
    g = _fsub_rows(a, b)
    f = _fadd_rows(c, g)
    return (
        _fmul_rows(e, f),
        _fmul_rows(g, h),
        _fmul_rows(f, g),
        _fmul_rows(e, h),
    )


def _fcanon_rows(x):
    x = _carry_rows(x)
    for _ in range(2):
        borrow = None
        out = []
        for k in range(NLIMB):
            v = x[k] - _P_L[k] - (borrow if borrow is not None else 0)
            out.append(v & M15)
            borrow = (v >> 15) & 1
        ge = borrow == 0
        x = [jnp.where(ge, out[k], x[k]) for k in range(NLIMB)]
    return x


def _finv_rows(z):
    def rep_sq(x, n):
        # rolled loop to bound code size; x stacked to (17, TB) for carry
        def body(_, v):
            return jnp.stack(_fsq_rows([v[k] for k in range(NLIMB)]))

        if n <= 4:
            for _ in range(n):
                x = _fsq_rows(x)
            return x
        stacked = jax.lax.fori_loop(0, n, body, jnp.stack(x))
        return [stacked[k] for k in range(NLIMB)]

    z2 = _fsq_rows(z)
    z9 = _fmul_rows(rep_sq(z2, 2), z)
    z11 = _fmul_rows(z9, z2)
    z_5_0 = _fmul_rows(_fsq_rows(z11), z9)
    z_10_0 = _fmul_rows(rep_sq(z_5_0, 5), z_5_0)
    z_20_0 = _fmul_rows(rep_sq(z_10_0, 10), z_10_0)
    z_40_0 = _fmul_rows(rep_sq(z_20_0, 20), z_20_0)
    z_50_0 = _fmul_rows(rep_sq(z_40_0, 10), z_10_0)
    z_100_0 = _fmul_rows(rep_sq(z_50_0, 50), z_50_0)
    z_200_0 = _fmul_rows(rep_sq(z_100_0, 100), z_100_0)
    z_250_0 = _fmul_rows(rep_sq(z_200_0, 50), z_50_0)
    return _fmul_rows(rep_sq(z_250_0, 5), z11)


def _verify_kernel(ax_ref, ay_ref, ry_ref, rsign_ref, sbits_ref, hbits_ref, out_ref):
    # lane tile is (S, 128): one full (8,128) vreg per limb row when S=8
    S, LANES = ax_ref.shape[1], ax_ref.shape[2]

    def rows(ref):
        return [ref[k] for k in range(NLIMB)]

    def const_rows(vals):
        return [jnp.full((S, LANES), v, dtype=jnp.int32) for v in vals]

    zero = jnp.zeros((S, LANES), dtype=jnp.int32)
    one_v = jnp.ones((S, LANES), dtype=jnp.int32)
    zeros = [zero] * NLIMB
    one = [one_v] + [zero] * (NLIMB - 1)

    ax = rows(ax_ref)
    ay = rows(ay_ref)
    d2_rows = const_rows(_D2_L)

    nax = _fsub_rows(zeros, ax)
    neg_a = (nax, ay, one, _fmul_rows(nax, ay))
    b_pt = (const_rows(_BX_L), const_rows(_BY_L), one, const_rows(_BT_L))
    b_neg_a = _point_add_rows(b_pt, neg_a, d2_rows)
    ident = (zeros, one, one, zeros)

    def pack(pt):
        return jnp.stack([jnp.stack(coord) for coord in pt])  # (4,17,TB)

    tab_ident = pack(ident)
    tab_b = pack(b_pt)
    tab_na = pack(neg_a)
    tab_bna = pack(b_neg_a)

    def unpack(arr):
        return tuple([arr[c][k] for k in range(NLIMB)] for c in range(4))

    def step(i, acc_arr):
        acc = unpack(acc_arr)
        acc = _point_double_rows(acc)
        # bits stored MSB-first row 0 = bit 252
        sb = sbits_ref[i]
        hb = hbits_ref[i]
        sel = sb + 2 * hb
        addend_arr = jnp.where(
            (sel == 0)[None, None], tab_ident,
            jnp.where(
                (sel == 1)[None, None], tab_b,
                jnp.where((sel == 2)[None, None], tab_na, tab_bna),
            ),
        )
        res = _point_add_rows(acc, unpack(addend_arr), d2_rows)
        return pack(res)

    acc_arr = jax.lax.fori_loop(0, 253, step, pack(ident))
    px, py, pz, _ = unpack(acc_arr)
    zinv = _finv_rows(pz)
    x_aff = _fcanon_rows(_fmul_rows(px, zinv))
    y_aff = _fcanon_rows(_fmul_rows(py, zinv))
    ry = _fcanon_rows(rows(ry_ref))
    eq = jnp.ones((S, LANES), dtype=jnp.bool_)
    for k in range(NLIMB):
        eq = eq & (y_aff[k] == ry[k])
    eq = eq & ((x_aff[0] & 1) == rsign_ref[0])
    out_ref[0] = eq.astype(jnp.int32)


def _make_verify(s_tile: int, interpret: bool):
    """Inputs shaped (rows, S, 128) with the batch laid out as (S, 128)
    lane tiles; the grid walks S in s_tile chunks."""

    def call(ax, ay, ry, rsign, sbits, hbits):
        s_total = ax.shape[1]
        spec17 = pl.BlockSpec((NLIMB, s_tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
        spec253 = pl.BlockSpec((253, s_tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
        spec1 = pl.BlockSpec((1, s_tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM)
        return pl.pallas_call(
            _verify_kernel,
            grid=(s_total // s_tile,),
            in_specs=[spec17, spec17, spec17, spec1, spec253, spec253],
            out_specs=spec1,
            out_shape=jax.ShapeDtypeStruct((1, s_total, 128), jnp.int32),
            interpret=interpret,
        )(ax, ay, ry, rsign, sbits, hbits)

    return jax.jit(call)


_verify_calls: dict = {}


def _get_verify(tb: int, interpret: bool):
    key = (tb, interpret)
    if key not in _verify_calls:
        _verify_calls[key] = _make_verify(tb, interpret)
    return _verify_calls[key]


def built_interpret_modes() -> list[bool]:
    """interpret= of every pallas_call this process has built (what the
    device daemon reports for a Pallas kernel it serves)."""
    return sorted({key[1] for key in _verify_calls})


def _interpret() -> bool:
    """Decided from the backend of the process that runs the kernel
    (ops/gateway.pallas_interpret): never from a platform guess."""
    from tendermint_tpu.ops.gateway import pallas_interpret

    return pallas_interpret()


S_TILE = 8  # (8, 128) = one full int32 vreg per limb row


def verify_batch(items: list[tuple[bytes, bytes, bytes]]) -> np.ndarray:
    """Drop-in replacement for ops.ed25519.verify_batch using the Pallas
    kernel (interpret mode off-TPU so tests run on CPU)."""
    n = len(items)
    if n == 0:
        return np.zeros(0, dtype=bool)
    interpret = _interpret()
    tile_lanes = S_TILE * 128
    bucket = ((n + tile_lanes - 1) // tile_lanes) * tile_lanes
    s_total = bucket // 128
    ax, ay, ry, rs, s_bits, h_bits, valid = base.prepare_batch(items, bucket)
    # kernel expects bits MSB-first rows; reshape batch to (S, 128) tiles
    s_rev = np.ascontiguousarray(s_bits[::-1]).reshape(253, s_total, 128)
    h_rev = np.ascontiguousarray(h_bits[::-1]).reshape(253, s_total, 128)
    fn = _get_verify(S_TILE, interpret)
    ok = fn(
        jnp.asarray(ax.reshape(NLIMB, s_total, 128)),
        jnp.asarray(ay.reshape(NLIMB, s_total, 128)),
        jnp.asarray(ry.reshape(NLIMB, s_total, 128)),
        jnp.asarray(rs.reshape(1, s_total, 128)),
        jnp.asarray(s_rev), jnp.asarray(h_rev),
    )
    return (np.asarray(ok).reshape(-1)[:n] != 0) & valid[:n]
