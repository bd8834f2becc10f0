"""Doubling-free batched Ed25519 verify: per-validator comb tables + a
fixed-base MXU comb — the round-5 TPU kernel.

WHY. The f32/f32p ladders spend ~85% of their VPU work on the 254 point
doublings every signature pays (ops/ed25519_f32p.py header). Those
doublings are per-lane bilinear ops — a systolic matmul unit cannot share
weights across them, so the MXU idles while the VPU grinds. But the
consensus workload has structure the reference's per-sig loop
(types/validator_set.go:247-250) never exploits: THE SAME VALIDATOR KEYS
SIGN EVERY BLOCK. Precompute, once per key, a windowed multiple table of
the negated pubkey on device, and every later verification of that key
needs ZERO doublings:

    [s]B + [h](-A)  ==  sum_p T_B[p][s_p]  +  sum_p T_A[p][h_p]

with 4-bit windows: 64 positions per scalar, 16 entries each, so a verify
is 128 table lookups + 127 mixed (niels) point additions — ~3x fewer VPU
ops than the 127-step joint Straus ladder. The two halves engage the
hardware differently:

- [h](-A): per-lane gather from a device-resident POOL of per-validator
  tables (bf16 rows; 8-bit limbs are exact in bf16). HBM-bandwidth work.
  The pool is (C*64, 1536): a row is one window position of one key, its
  16 entries x 96 limbs. A 1536-wide row is 12 whole 128-lane tiles, so
  the device stores the pool row-major and unpadded and the program
  gathers rows where they lie. A 96-wide row pads to 128, so the device
  stores such a pool transposed, and a program that gathers its rows
  copies it whole first, on every call (2.4 GB read, 3.2 GB written at
  12,288 slots).
- [s]B: one-hot(digit) x fixed-basis-table matmuls via dot_general with
  bf16 inputs and fp32 accumulation — the MXU path. Exact: one-hot is
  0/1, table limbs are <= 255 (both exact bf16), the MXU multiplies bf16
  exactly and accumulates fp32 over 16 terms of <= 255 each.

Amortization: building one validator's table costs ~13 verifies' worth of
device work (896 adds + 256 doubles + batch normalization), amortized
over every subsequent block that validator signs — hundreds to millions
of verifies in steady state. Unknown-key or tiny batches stay on the
existing kernels/CPU path (the gateway keeps its fallback semantics).

Verification math and accept/reject semantics are IDENTICAL to
ops/ed25519_f32.py (strict cofactorless RFC 8032: compare y(W) and
sign-x(W) against R), and all field arithmetic reuses the f32 radix-2^8
machinery, so its EXACTNESS ARGUMENT carries over; the one new formula
(niels mixed add) is bounds-checked in the docstring of _niels_add.

Reference hot loops this replaces: types/vote_set.go:175,
types/validator_set.go:247-250, blockchain/reactor.go:235.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from array import array
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import ed25519 as ed_ref
from tendermint_tpu.devd_spans import mark, note
from tendermint_tpu.ops import ed25519_f32 as base

logger = logging.getLogger("ops.ed25519_comb")

P = base.P
NL = base.NL
W_POS = 64  # 4-bit windows over 256 bits
W_ENT = 16  # entries per window (digit values 0..15)
COORD_ROWS = 3 * NL  # niels coords per entry: (y-x, y+x, 2dxy), 32 limbs each
POOL_ROW = W_ENT * COORD_ROWS  # one window position of a key's table: 1536


# ---------------------------------------------------------------------------
# fixed-base table for B (host-computed once, python ints)
# ---------------------------------------------------------------------------

_b_table_cache: list = []
_b_table_lock = threading.Lock()


def _niels_rows_np(x: int, y: int) -> np.ndarray:
    """(96,) float32 canonical limbs of ((y-x) mod p, (y+x) mod p,
    (2d*x*y) mod p)."""
    t2 = (2 * ed_ref.D % P) * x % P * y % P
    out = np.empty(COORD_ROWS, dtype=np.float32)
    out[:NL] = base._int_to_limbs_const((y - x) % P)
    out[NL : 2 * NL] = base._int_to_limbs_const((y + x) % P)
    out[2 * NL :] = base._int_to_limbs_const(t2)
    return out


def b_table() -> np.ndarray:
    """(W_POS, W_ENT, 96) float32 niels table of v * 16^p * B. Entry 0 is
    the identity in niels form: (1, 1, 0)."""
    with _b_table_lock:
        if _b_table_cache:
            return _b_table_cache[0]
        tab = np.zeros((W_POS, W_ENT, COORD_ROWS), dtype=np.float32)
        ident = np.zeros(COORD_ROWS, dtype=np.float32)
        ident[0] = 1.0
        ident[NL] = 1.0
        gp = ed_ref.B  # extended (X, Y, Z=1, T)
        for p in range(W_POS):
            tab[p, 0] = ident
            acc = gp
            for v in range(1, W_ENT):
                ax, ay = base._affine(acc)
                tab[p, v] = _niels_rows_np(ax, ay)
                if v + 1 < W_ENT:
                    acc = ed_ref.point_add(acc, gp)
            for _ in range(4):  # gp <- 16 * gp
                gp = ed_ref.point_add(gp, gp)
        _b_table_cache.append(tab)
        return tab


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------


def _digits4(limbs_u8: jax.Array) -> jax.Array:
    """(32,B) int32 byte limbs -> (64,B) int32 4-bit digits, little-endian
    position order (position p has weight 16^p)."""
    lo = limbs_u8 & 15
    hi = (limbs_u8 >> 4) & 15
    return jnp.stack([lo, hi], axis=1).reshape(2 * NL, limbs_u8.shape[-1])


def _niels_add(acc, my, py, t2):
    """Mixed addition acc + N where N is a niels-form affine point
    (my = y-x, py = y+x, t2 = 2d*x*y; implicit z = 1).

    BOUNDS (under the f32 EXACTNESS ARGUMENT's loose-limb invariants):
    my/py/t2 are canonical (limbs <= 255) — tighter than any operand the
    argument already covers, so a/b/c row sums are <= the point_add
    bounds; d = fadd(z1, z1) matches point_add's d; e..h and the closing
    four muls are literally point_add's closing pattern. Nothing exceeds
    the documented 2^23.5 ceiling."""
    x1, y1, z1, t1 = acc
    a = base.fmul(base.fsub(y1, x1), my)
    b = base.fmul(base.fadd(y1, x1), py)
    c = base.fmul(t1, t2)
    d = base.fadd(z1, z1)
    e = base.fsub(b, a)
    f = base.fsub(d, c)
    g = base.fadd(d, c)
    h = base.fadd(b, a)
    return (
        base.fmul(e, f),
        base.fmul(g, h),
        base.fmul(f, g),
        base.fmul(e, h),
    )


def _pool_entries(pool, slots, dh):
    """The entry of digit dh[p, b] at position p of lane b's table, for
    all 64 positions: (64, 96, B) f32.

    Gathers the 64 rows of each lane's slot whole (a row is tile-aligned;
    a 96-wide entry at digit*96 is not), zeroes every entry but the
    digit's, and folds the 16 entries of a row onto one with a 0/1 (1536,
    96) matrix on the MXU: exact, as one term of each sum is not zero and
    it is an integer <= 255. Nothing splits a row into (16, 96): that view
    pads 96 to 128, so the compiler lays the data out anew: for the pool
    itself a copy of the whole pool on every call (a (C*1024, 96) pool is
    stored column-major and copied so), for the gathered rows 2-3x the
    device time of this fold at 256 lanes (estimated cycles of the program
    compiled for a v5e)."""
    batch = slots.shape[0]
    pos = jnp.arange(W_POS, dtype=jnp.int32)[:, None]  # (64,1)
    rows = jnp.take(pool, (slots[None, :] * W_POS + pos).reshape(-1), axis=0)
    rows = rows.reshape(W_POS, batch, POOL_ROW)  # (64, B, 1536)
    col = jnp.arange(POOL_ROW, dtype=jnp.int32)
    picked = jnp.where(col // COORD_ROWS == dh[:, :, None], rows,
                       jnp.zeros((), rows.dtype))
    fold = (jnp.arange(COORD_ROWS, dtype=jnp.int32)[:, None]
            == col % COORD_ROWS).astype(jnp.bfloat16)  # (96, 1536)
    # batched over positions, as [s]B's dot is: XLA:CPU runs no bf16 x
    # bf16 -> f32 dot without a batch dimension
    return jax.lax.dot_general(
        jnp.broadcast_to(fold, (W_POS,) + fold.shape),  # (64, 96, 1536)
        picked,  # (64, B, 1536)
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _verify_comb_impl(pool, t_b, slots, r_y, r_sign, s8, h8):
    """pool: (C*W_POS, POOL_ROW) bf16 per-validator niels tables (of -A),
    one row a (slot, window position) holding its 16 entries (CombPool);
    t_b: (W_POS, W_ENT, 96) f32 fixed-base table; slots: (B,) int32 pool
    slot per lane; r_y/r_sign/s8/h8 as in base._verify_impl. -> bool[B].

    Accumulates W = [s]B + [h](-A) as 128 niels lookups + 127 mixed adds
    (no doublings), then compares against R exactly like the ladder
    kernels."""
    dh = _digits4(h8)  # (64,B) digits of h -> per-validator pool
    ds = _digits4(s8)  # (64,B) digits of s -> fixed-base table

    rows_a = _pool_entries(pool, slots, dh)  # [h](-A): (64, 96, B)

    # [s]B: one-hot x basis-table batched matmul (MXU: bf16 inputs, fp32
    # accumulation; exact for 0/1 x <=255 integer operands)
    oh = (ds[:, None, :] == jnp.arange(W_ENT, dtype=jnp.int32)[None, :, None])
    rows_b = jax.lax.dot_general(
        t_b.astype(jnp.bfloat16),  # (64, 16, 96)
        oh.astype(jnp.bfloat16),  # (64, 16, B)
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (64, 96, B)

    stream = jnp.concatenate([rows_a, rows_b], axis=0)  # (128, 96, B)

    zeros = stream[0, :NL] * 0.0
    one = zeros.at[0].set(1.0)
    ident = (zeros, one, one, zeros)

    def step(acc, row):
        return _niels_add(acc, row[:NL], row[NL : 2 * NL], row[2 * NL :]), None

    acc, _ = jax.lax.scan(step, ident, stream)

    px, py_, pz, _ = acc
    zinv = base.finv(pz)
    x_aff = base.fcanon(base.fmul(px, zinv))
    y_aff = base.fcanon(base.fmul(py_, zinv))
    sign = x_aff[0].astype(jnp.int32) & 1
    return jnp.all(y_aff == base.fcanon(r_y), axis=0) & (sign == r_sign)


_verify_jit = jax.jit(_verify_comb_impl)


# -- table building on device -------------------------------------------------


def _d2(width: int) -> jax.Array:
    return jnp.broadcast_to(jnp.asarray(base._D2)[:, None], (NL, width))


def _multiples(q):
    """v*Q for v = 1..15 of the extended points q ((32, w) coordinates),
    as (32, 15*w) coordinates with lanes (v-1, lane): a tree of four
    levels, each one point_add over every multiple it makes at once
    (2 = 1+1; 3, 4 = 2+{1, 2}; 5..8 = 4+{1..4}; 9..15 = 8+{1..7})."""
    w = q[0].shape[-1]
    mult, m = q, 1
    while m < W_ENT - 1:
        k = min(m, W_ENT - 1 - m)
        top = tuple(jnp.tile(c[:, (m - 1) * w:m * w], (1, k)) for c in mult)
        low = tuple(c[:, :k * w] for c in mult)
        new = base.point_add(top, low, _d2(k * w))
        mult = tuple(jnp.concatenate(ab, axis=1) for ab in zip(mult, new))
        m += k
    return mult


def _split(x, n: int):
    return tuple(x[:, i:i + n] for i in range(0, x.shape[-1], n))


def _double_wide(p):
    """base.point_double with its four squarings as ONE multiplication
    over 4n lanes and its four closing products as another: the same
    operations on the same operands, two steps of the chain where
    point_double takes eight."""
    x1, y1, z1, _ = p
    n = x1.shape[-1]
    s = jnp.concatenate([x1, y1, z1, base.fadd(x1, y1)], axis=1)
    a, b, zz, xy2 = _split(base.fmul(s, s), n)
    c = base.fadd(zz, zz)
    h = base.fadd(a, b)
    e = base.fsub(h, xy2)
    g = base.fsub(a, b)
    f = base.fadd(c, g)
    return _split(base.fmul(jnp.concatenate([e, g, f, e], axis=1),
                            jnp.concatenate([f, h, g, h], axis=1)), n)


def _fcanon_dense(x):
    """base.fcanon of a wide (32, L) array, L a multiple of 8, computed as
    (32, 8, L/8): its limb-by-limb carry passes then work on whole (8,
    128) tiles, where a (1, L) row fills one sublane of eight."""
    return base.fcanon(x.reshape(NL, 8, -1)).reshape(x.shape)


def _montgomery(zs, invert):
    """Montgomery's batch inversion along the leading axis of zs (k, 32,
    w): one chain of prefix products, `invert` of their total, one chain
    back. -> the (k, 32, w) inverses."""
    one = (zs[0] * 0.0).at[0].set(1.0)

    def fwd(acc, z):
        return base.fmul(acc, z), acc      # the prefix BEFORE z

    total, prefix = jax.lax.scan(fwd, one, zs)

    def bwd(acc, inp):                     # acc: 1 / (prefix AFTER z)
        z, pre = inp
        return base.fmul(acc, z), base.fmul(acc, pre)

    _, inv = jax.lax.scan(bwd, invert(total), (zs, prefix), reverse=True)
    return inv


# Entries a Montgomery chain of the build covers side by side: the 960 Zs
# of a key are 32 groups of 30, each group a lane block of its own
INV_GROUPS = 32


def _batch_inverse(z, n: int):
    """z: (32, E*n) with lanes (entry, key), E a multiple of INV_GROUPS.
    The entries form INV_GROUPS groups; the groups' chains run together
    on (32, INV_GROUPS*n) lanes, and their totals are inverted by a second
    chain of INV_GROUPS steps around one finv of n lanes. The grouping
    changes neither the work (3 multiplications an entry) nor the
    temporaries (the prefixes are E*n elements whatever the split), only
    the depth: 2*E/G + 2*G + finv, least at G near sqrt(E) for every n."""
    g = INV_GROUPS
    m = z.shape[-1] // (g * n)
    zs = z.reshape(NL, m, g * n).transpose(1, 0, 2)

    def invert_totals(total):              # (32, g*n), lanes (group, key)
        t = total.reshape(NL, g, n).transpose(1, 0, 2)
        inv = _montgomery(t, base.finv)
        return inv.transpose(1, 0, 2).reshape(NL, g * n)

    inv = _montgomery(zs, invert_totals)
    return inv.transpose(1, 0, 2).reshape(z.shape)


# The most keys one pass of the build takes: a wider build runs as passes
# of this many keys, one after another inside the same program
BUILD_KEYS = 128


def _build_tables_impl(qx, qy):
    """qx/qy: (32, n) f32 canonical affine limbs of Q = -A per validator.
    Returns (n, W_POS*W_ENT, 96) float32 niels tables (canonical limbs,
    ready for a bf16 cast): row p*16 + v is v * 16^p * Q, row p*16 the
    identity (1, 1, 0).

    WHY THE SHAPE. A field multiplication over a few hundred lanes costs
    the chip about a microsecond whatever the lanes hold, so a build of 1
    to 128 keys pays for how many steps lie in series, not for its work.
    The first form (PR 35) had 3,184 sequential loop steps (a scan of the
    64 positions, each 14 chained adds and 4 doublings; a 960-step
    Montgomery scan each way; a 960-step map to niels rows; finv's loops)
    and took the chip 39.9 ms at 128 keys and 467 ms at 1. This one does
    the same arithmetic in 428 steps, the work laid side by side on the
    lane axis instead (_build_keys): 9.2 ms at 128 keys and 9.0 at 1; 18.2
    at 216 (50.6 before), 70.7 at 1,024 (104.6) (my chip runs, PR 36,
    host clock around the program; 8.4 ms of device time at 128 in a
    traced run of ycsb-a.steady, 38.2 before: ledger, PR 35).

    Past BUILD_KEYS keys the lanes are full and the work is what costs:
    such a build runs as passes of BUILD_KEYS keys (a lax.map; the last
    pass padded with the last key, whose tables are dropped), so a step
    never spans more than BUILD_KEYS keys' lanes and the temporaries stay
    those of one pass (one pass over 1,024 keys took 143-178 ms in the
    forms tried, passes 70.7: my chip runs, PR 36)."""
    n = qx.shape[-1]
    if n <= BUILD_KEYS:
        return _build_keys(qx, qy)
    passes = -(-n // BUILD_KEYS)
    pad = passes * BUILD_KEYS - n
    q = jnp.pad(jnp.stack([qx, qy]), ((0, 0), (0, 0), (0, pad)), mode="edge")
    q = q.reshape(2, NL, passes, BUILD_KEYS).transpose(2, 0, 1, 3)
    tables = jax.lax.map(lambda c: _build_keys(c[0], c[1]), q)
    return tables.reshape(passes * BUILD_KEYS, W_POS * W_ENT, COORD_ROWS)[:n]


def _build_keys(qx, qy):
    """_build_tables_impl for at most BUILD_KEYS keys, in four phases, each
    under its `jax.named_scope` (the device trace splits a build by them):

    - q_chain: a scan of 64 steps, 4 doublings each, carrying Q_p =
      16^p * Q: the one chain that is sequential by nature; a doubling is
      two multiplications over 4n lanes (_double_wide).
    - multiples: v*Q_p for all 64 positions at once on (32, 64n) lanes,
      four levels of point_add (_multiples).
    - batch_inverse: the 960 Zs of a key in 32 groups side by side
      (_batch_inverse): 2*30 + 2*32 steps and one finv.
    - niels: one pass over all 960n entries, fcanon on whole tiles
      (_fcanon_dense).

    Every field operation is one the f32 EXACTNESS ARGUMENT already
    covers, on operands of the same classes as before (point_add of
    point_add/point_double outputs, the doubling's own formula, fmul of
    fmul outputs, fcanon of fadd/fsub/fmul outputs), and the output is
    canonical: the tables are the first form's bit for bit
    (tests/test_ops_comb.py)."""
    n = qx.shape[-1]
    one = (qx * 0.0).at[0].set(1.0)

    with jax.named_scope("q_chain"):
        def dbl4(q, _):
            nxt = q
            for _ in range(4):
                nxt = _double_wide(nxt)
            return nxt, q

        _, qs = jax.lax.scan(dbl4, (qx, qy, one, base.fmul(qx, qy)), None,
                             length=W_POS)
        # (64, 32, n) a coordinate -> (32, 64n), lanes (position, key)
        qp = tuple(c.transpose(1, 0, 2).reshape(NL, W_POS * n) for c in qs)

    with jax.named_scope("multiples"):
        ext = _multiples(qp)               # lanes (v-1, position, key)

    with jax.named_scope("batch_inverse"):
        zinv = _batch_inverse(ext[2], n)

    with jax.named_scope("niels"):
        x = base.fmul(ext[0], zinv)
        y = base.fmul(ext[1], zinv)
        t2 = base.fmul(base.fmul(x, y), _d2(x.shape[-1]))
        rows = jnp.stack([_fcanon_dense(base.fsub(y, x)),
                          _fcanon_dense(base.fadd(y, x)), _fcanon_dense(t2)])
        rows = rows.reshape(3, NL, W_ENT - 1, W_POS, n)
        niels = rows.transpose(4, 3, 2, 0, 1).reshape(
            n, W_POS, W_ENT - 1, COORD_ROWS)
        ident = jnp.zeros((n, W_POS, 1, COORD_ROWS), dtype=jnp.float32)
        ident = ident.at[..., 0].set(1.0).at[..., NL].set(1.0)
        full = jnp.concatenate([ident, niels], axis=2)  # (n, 64, 16, 96)
        return full.reshape(n, W_POS * W_ENT, COORD_ROWS)


_build_jit = jax.jit(_build_tables_impl)


def _scatter_tables(pool, slots, tables):
    """The closed pool's install: `tables` (n, 1024, 96) f32 into the
    slots `slots` of a new pool beside `pool` (C*64, 1536), through a
    (C, 64, 1536) view, which tiles without padding."""
    rows = tables.astype(jnp.bfloat16).reshape(-1, W_POS, POOL_ROW)
    pool3 = pool.reshape(-1, W_POS, POOL_ROW)
    return pool3.at[slots].set(rows).reshape(pool.shape)


_scatter_jit = jax.jit(_scatter_tables)


def _update_pool_impl(pool, slots, tables):
    """The open population's pool update: write `tables` (n, 1024, 96)
    f32 over the slots `slots` of the pool (C*64, 1536) bf16, a key's
    table as its 64 rows of (16 entries x 96). The pool is DONATED: the
    slots are written in place, no second pool exists at any instant, and
    the runtime orders the write behind every program that was handed the
    old buffer before this call. One dynamic-update-slice a key, in a
    loop (tests/test_chip_compile.py holds it to no second pool). The
    build's (n, 1024, 96) tables are laid out as rows here, in this
    program, not in the build: the two forms' estimated cycles differ by
    0.4% (the programs compiled for a v5e)."""
    tables = tables.astype(jnp.bfloat16).reshape(-1, W_POS, POOL_ROW)

    def write(i, p):
        return jax.lax.dynamic_update_slice(p, tables[i], (slots[i] * W_POS, 0))

    return jax.lax.fori_loop(0, slots.shape[0], write, pool)


_update_jit = jax.jit(_update_pool_impl, donate_argnums=(0,))

# An open population (TENDERMINT_TPU_COMB_OPEN=1: more keys than slots,
# misses all day) runs its miss programs at ONE size and no other: a build
# and its pool update pad their new keys up to the pool's `miss_bucket`
# (the padding lanes write the reserved slot 0), the first-sight ladder
# pads its lanes the same way, and a count past the bucket is several
# programs. So the programs that exist are the ones `compile_miss_programs`
# ran at the claim, whatever the traffic. The bucket is 128, on every
# backend: a narrower program costs about the same device time (the build
# 9.0 ms at 1 key and 9.2 at 128, the chain of steps and not the lanes
# setting it; the ladder's lanes lie on the 128-wide minor axis: my chip
# runs, PR 35 and 36, PERF.md section 6) and 8-17 s more of every claim.
# Only a pool with fewer slots than that (a test's) builds at its own
# size: one batch cannot hold more new keys than the pool has slots.
MISS_BUCKET = 128
# ... and its comb program at no more than this many lanes (the widest
# bucket the serving path keeps warm: devd.MERGE_MAX_LANES): a wider batch
# is served as batches of this width. One slow height makes a block of 300
# updates, and a 512-lane program compiled under the pool's lock held every
# verifier call for its 20 s (my chip run, PR 35: 2,949 of 8,640 failed).
OPEN_MAX_LANES = 256

SLOT_BYTES = W_POS * W_ENT * COORD_ROWS * 2  # one key's table, bf16


def open_population() -> bool:
    return os.environ.get("TENDERMINT_TPU_COMB_OPEN", "") == "1"


def _chunks(n: int, bucket: int):
    """(start, count) of the programs of `bucket` lanes that cover n."""
    for at in range(0, n, bucket):
        yield at, min(bucket, n - at)


# ---------------------------------------------------------------------------
# the pool manager
# ---------------------------------------------------------------------------


class PoolExhausted(RuntimeError):
    """One batch references more distinct validator keys than the pool's
    maximum capacity; the caller should use a ladder kernel instead."""


def _neg_x_bytes(x_le: bytes) -> bytes:
    x = int.from_bytes(x_le, "little")
    return ((P - x) % P).to_bytes(32, "little")


class CombPool:
    """Device-resident LRU pool of per-validator comb tables.

    Slots are leased to pubkeys on first sight; the table build runs on
    device, batched across all new keys in the request. Eviction is LRU.

    The array (`_pool`) is (C*W_POS, POOL_ROW) bf16: slot s is rows
    s*64 .. s*64+63, row s*64+p window position p of its key's table with
    the 16 entries of 96 limbs side by side (a reshape of a slot's rows
    gives its (1024, 96) table). That shape is the one the v5e stores
    row-major and unpadded, so the comb program gathers from the pool in
    place; a (C*1024, 96) array, the same bytes, is stored column-major
    (96 would pad to 128) and costs a copy of the whole pool every call.

    Two populations, chosen where the pool is made (the daemon's
    configuration: TENDERMINT_TPU_COMB_OPEN):

    - closed (the default: a validator set and a few hundred signers,
      every key resident after warm-up). Capacity grows by doubling up to
      `cap` (env TENDERMINT_TPU_COMB_CAP, default 12288 slots ~= 2.4 GB
      bf16 — sized for the 10k-validator benchmark on a 16 GB v5e); new
      keys build in one program of their exact count and the pool array
      is rebuilt functionally (no donation: an in-flight verify may still
      reference the old buffer).
    - open (more keys than slots: the pool misses and evicts all day).
      The pool holds `cap` slots from the start, so every program has ONE
      pool shape; new keys build in programs of `miss_bucket` keys and a
      donated update writes their slots in place (`_update_pool_impl`).
      Nothing reads a half-written pool: a batch leases its slots, builds,
      updates and DISPATCHES its verify under the pool's lock (the caller
      holds it: `verify_batch_async`), so between a lease and the program
      that reads it no other batch can evict, and on the device a program
      runs behind every update dispatched before it and ahead of every
      one after. The pool also keeps a log of its batches (`log_batch`)
      for a plain model to be checked against."""

    def __init__(self, capacity: int | None = None,
                 max_capacity: int | None = None,
                 open_pop: bool | None = None):
        self.cap = int(
            max_capacity
            or os.environ.get("TENDERMINT_TPU_COMB_CAP", 12288)
        )
        self.open = open_population() if open_pop is None else bool(open_pop)
        c0 = int(capacity or (self.cap if self.open else min(self.cap, 256)))
        self._c = c0
        self.miss_bucket = min(MISS_BUCKET, c0)
        self._pool = jnp.zeros((c0 * W_POS, POOL_ROW), dtype=jnp.bfloat16)
        self._lru: OrderedDict[bytes, int] = OrderedDict()
        self._free: list[int] = list(range(c0 - 1, 0, -1))  # slot 0 reserved
        self._lock = threading.RLock()
        self._tb = jnp.asarray(b_table())
        # builds: table-build programs run; build_keys: keys they built;
        # ladders: first-sight ladder programs run (an open population's);
        # lanes_*: how the lanes shown to the kernel were served (a key
        # resident / a key on the ladder before its MIN_SIGHT-th batch / a
        # key built in that batch)
        self.stats = {"builds": 0, "build_keys": 0, "evictions": 0, "grows": 0,
                      "lanes_hit": 0, "lanes_first_sight": 0, "lanes_built": 0,
                      "ladders": 0}
        # what the last ensure() did, for the call's record and the log
        # (read outside the lock by a closed pool's caller: a record's
        # counts there can be a concurrent call's; an open pool's cannot)
        self.last: dict = {}
        self._evicted_ever: set[bytes] = set()
        # the open pool's log of batches (log_batch / dump_log)
        self._log: list | None = [] if self.open else None
        self._log_lanes = 0
        self._ids: dict[bytes, int] = {}

    @property
    def capacity(self) -> int:
        return self._c

    def _grow(self) -> None:
        new_c = min(self._c * 2, self.cap)
        if new_c == self._c:
            return
        pad = jnp.zeros(((new_c - self._c) * W_POS, POOL_ROW),
                        dtype=jnp.bfloat16)
        self._pool = jnp.concatenate([self._pool, pad], axis=0)
        self._free.extend(range(new_c - 1, self._c - 1, -1))
        self._c = new_c
        self.stats["grows"] += 1

    def _take_slot(self, pinned: set[int], evicted: list[bytes]) -> int:
        if not self._free:
            self._grow()
        if self._free:
            return self._free.pop()
        # evict LRU (front of the OrderedDict) — but never a slot leased
        # to another lane of the batch currently being assembled: that
        # lane's slots[] entry would silently point at the new key's
        # table and reject a valid signature.
        for key, slot in self._lru.items():
            if slot not in pinned:
                del self._lru[key]
                self.stats["evictions"] += 1
                if self._logging():   # the log tells built from rebuilt
                    self._evicted_ever.add(key)
                evicted.append(key)
                return slot
        raise PoolExhausted(
            f"batch needs more distinct validator keys than the comb "
            f"pool's max capacity ({self.cap} slots)"
        )

    def ensure(self, keys: list[bytes], xs: np.ndarray, ys: np.ndarray):
        """Lease slots for decompressed keys. keys[i] is the 32-byte
        compressed pubkey; xs/ys are (n, 32) u8 canonical affine limbs of
        A (NOT negated — negation happens here). Returns
        (slots int32 (n,), the pool array to verify against: a snapshot
        in a closed pool; in an open one the array as it stands, good
        until the caller lets go of the pool's lock). Caller must pass
        only keys whose decompression succeeded. Raises PoolExhausted when
        one batch holds more distinct keys than max capacity (the gateway
        backend falls back to the ladder kernel)."""
        with self._lock:
            missing: dict[bytes, int] = {}
            first_at: dict[bytes, int] = {}
            pinned: set[int] = set()
            evicted: list[bytes] = []
            slots = np.zeros(len(keys), dtype=np.int32)
            try:
                for i, k in enumerate(keys):
                    s = self._lru.get(k)
                    if s is not None:
                        self._lru.move_to_end(k)
                        slots[i] = s
                        pinned.add(s)
                        continue
                    s = missing.get(k)
                    if s is None:
                        s = self._take_slot(pinned, evicted)
                        missing[k] = s
                        first_at[k] = i
                        self._lru[k] = s
                        pinned.add(s)
                    slots[i] = s
            except PoolExhausted:
                # roll back this call's leases: the tables were never
                # built, and a leaked _lru entry would route the key's
                # NEXT batch onto a garbage slot table (valid signatures
                # rejected until restart) — round-5 review finding
                for k, s in missing.items():
                    if self._lru.get(k) == s:
                        del self._lru[k]
                    self._free.append(s)
                # what it evicted on the way stays evicted, and is logged
                self.last = {"leased": set(), "built": {}, "rebuilt": set(),
                             "evicted": evicted, "build_ns": 0, "update_ns": 0}
                raise
            self.last = last = {
                "leased": set(keys) if self.open else (), "built": missing,
                "evicted": evicted,
                "rebuilt": {k for k in missing if k in self._evicted_ever},
                "build_ns": 0, "update_ns": 0}
            n_built = sum(1 for k in keys if k in missing)
            self.stats["lanes_built"] += n_built
            self.stats["lanes_hit"] += len(keys) - n_built
            if missing:
                uniq = list(missing.keys())
                idx = [first_at[k] for k in uniq]
                qx = np.zeros((NL, len(uniq)), dtype=np.float32)
                qy = np.zeros((NL, len(uniq)), dtype=np.float32)
                for j, i in enumerate(idx):
                    nx = np.frombuffer(
                        _neg_x_bytes(xs[i].tobytes()), dtype=np.uint8
                    )
                    qx[:, j] = nx.astype(np.float32)
                    qy[:, j] = ys[i].astype(np.float32)
                tslots = np.asarray(
                    [missing[k] for k in uniq], dtype=np.int32
                )
                if self.open:
                    self._install_in_place(qx, qy, tslots, last)
                else:
                    self._install_rebuilt(qx, qy, tslots)
                self.stats["build_keys"] += len(uniq)
            return slots, self._pool

    def _install_rebuilt(self, qx, qy, tslots) -> None:
        """The closed pool's install: one build program of the exact
        count, the pool array rebuilt beside the old one."""
        tables = _build_jit(jnp.asarray(qx), jnp.asarray(qy))
        self._pool = _scatter_jit(self._pool, jnp.asarray(tslots), tables)
        self.stats["builds"] += 1

    def _install_in_place(self, qx, qy, tslots, last: dict) -> None:
        """The open pool's install: programs of `miss_bucket` keys, each
        followed by the donated update of its slots. Padding lanes repeat
        the first key and write slot 0, which no key is ever leased."""
        for at, count in _chunks(qx.shape[1], self.miss_bucket):
            pad = self.miss_bucket - count
            cx = np.pad(qx[:, at:at + count], ((0, 0), (0, pad)), mode="edge")
            cy = np.pad(qy[:, at:at + count], ((0, 0), (0, pad)), mode="edge")
            cs = np.pad(tslots[at:at + count], (0, pad))
            t0 = time.perf_counter_ns()
            tables = _build_jit(jnp.asarray(cx), jnp.asarray(cy))
            t1 = time.perf_counter_ns()
            self._pool = _update_jit(self._pool, jnp.asarray(cs), tables)
            t2 = time.perf_counter_ns()
            last["build_ns"] += t1 - t0
            last["update_ns"] += t2 - t1
            self.stats["builds"] += 1

    # -- the open pool's log ---------------------------------------------

    LOG_MAX_LANES = 1 << 22
    # a lane's route, as the log holds it
    ROUTES = ("malformed", "hit", "first_sight", "built", "rebuilt",
              "undecodable")

    def _logging(self) -> bool:
        return self._log is not None and self._log_lanes <= self.LOG_MAX_LANES

    def log_batch(self, keys: list, comb_lanes: set[int], last: dict) -> None:
        """One batch as the pool served it: every lane's key and route
        (an index into ROUTES) in lane order, and the keys evicted in
        order. `comb_lanes`: the lanes that rode tables; `last`: what
        ensure() did for them. Keys are numbered as they first appear
        (0: no key)."""
        if not self._logging():
            return
        built, rebuilt = last.get("built", {}), last.get("rebuilt", ())
        leased = last.get("leased", ())

        def route(i: int, k) -> int:
            if k is None:
                return 0
            if i not in comb_lanes:
                return 2
            if k not in leased:
                return 5
            return 1 if k not in built else 4 if k in rebuilt else 3

        ids = self._ids
        self._log.append((
            array("I", [0 if k is None else ids.setdefault(k, len(ids) + 1)
                        for k in keys]),
            bytes(route(i, k) for i, k in enumerate(keys)),
            [ids.setdefault(k, len(ids) + 1) for k in last.get("evicted", [])]))
        self._log_lanes += len(keys)

    def dump_log(self, path: str) -> str | None:
        """The log as JSON lines: a header (the routes' names, capacity,
        MIN_SIGHT, the keys by number), then {"k", "r", "e"} a batch.
        Never raises: the daemon is stopping."""
        with self._lock:
            if self._log is None:
                return None
            log, ids = list(self._log), dict(self._ids)
            whole = self._log_lanes <= self.LOG_MAX_LANES
        head = {"routes": list(self.ROUTES), "capacity": self._c,
                "usable_slots": self._c - 1, "min_sight": _min_sight(),
                "batches": len(log), "whole": whole,
                "keys": [k.hex() for k in sorted(ids, key=ids.get)]}
        try:
            with open(path + ".tmp", "w") as f:
                f.write(json.dumps(head) + "\n")
                for k, r, e in log:
                    f.write(json.dumps(
                        {"k": k.tolist(), "r": "".join(map(str, r)), "e": e},
                        separators=(",", ":")) + "\n")
            os.replace(path + ".tmp", path)
            return path
        except OSError:
            return None

    def table_b(self):
        return self._tb


_default_pool: list[CombPool] = []
_default_pool_lock = threading.Lock()


def default_pool() -> CombPool:
    with _default_pool_lock:
        if not _default_pool:
            _default_pool.append(CombPool())
        return _default_pool[0]


def set_default_pool(pool: CombPool) -> None:
    with _default_pool_lock:
        _default_pool.clear()
        _default_pool.append(pool)


def reset_default_pool() -> None:
    """Drop the process-wide pool (tests; also frees device memory)."""
    with _default_pool_lock:
        _default_pool.clear()
    with _seen_lock:
        _seen.clear()


# -- second-sight build policy ------------------------------------------------
#
# Building a key's comb table costs ~13 verifies of device work, paid off
# only if the key is seen again (validator keys sign every block; a
# mempool user key may never recur — reference mempool/mempool.go:166-205
# verifies each tx signature exactly once). Policy: build tables only for
# keys on their >= MIN_SIGHT-th batch appearance; lanes whose key has no
# table yet verify on the f32 ladder in the same call. Self-tuning, no
# caller hints: commits go all-comb from their second block, one-shot
# keys never trigger a build.

_seen: OrderedDict[bytes, int] = OrderedDict()
_seen_lock = threading.Lock()
_SEEN_CAP = 1 << 18


def _min_sight() -> int:
    return int(os.environ.get("TENDERMINT_TPU_COMB_MIN_SIGHT", "2"))


def _bump_seen(keys: set[bytes]) -> dict[bytes, int]:
    out = {}
    with _seen_lock:
        for k in keys:
            c = _seen.pop(k, 0) + 1
            _seen[k] = c
            out[k] = c
        while len(_seen) > _SEEN_CAP:
            _seen.popitem(last=False)
    return out


# ---------------------------------------------------------------------------
# gateway backend API
# ---------------------------------------------------------------------------


def _dispatch_comb(items, kidx, keys, pool_mgr):
    """Marshal + enqueue the comb kernel for items[kidx] (whose keys are
    all pool-eligible). Returns a resolver for bool[len(kidx)]."""
    sub = [items[i] for i in kidx]
    n = len(sub)
    bucket = base._next_pow2(n)
    ax, ay, ry, rs, s8, h8, valid = base.prepare_batch8(sub, bucket)
    slots = np.zeros(bucket, dtype=np.int32)
    vidx = [i for i in range(n) if valid[i]]
    if vidx:
        xs = ax.T[np.asarray(vidx)].astype(np.uint8)
        ys = ay.T[np.asarray(vidx)].astype(np.uint8)
        leased, pool_arr = pool_mgr.ensure(
            [keys[i] for i in vidx], xs, ys
        )
        slots[np.asarray(vidx)] = leased
    else:
        pool_arr = pool_mgr.ensure([], np.zeros((0, 32)), np.zeros((0, 32)))[1]
    last = pool_mgr.last
    if last["built"]:
        note(keys_built=len(last["built"]), slots_evicted=len(last["evicted"]),
             build_ns=last["build_ns"], update_ns=last["update_ns"])
    # the daemon's per-call record (devd_spans): arrays ready / the jit
    # call returned / verdicts on the host. One attribute test each where
    # no record is open, which is everywhere but inside devd.
    mark("marshal", bucket)
    ok_dev = _verify_jit(
        pool_arr,
        pool_mgr.table_b(),
        jnp.asarray(slots),
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


def verify_batch_async(items: list[tuple[bytes, bytes, bytes]]):
    """Marshal + enqueue; returns a zero-arg resolver for bool[B] — the
    standard kernel contract (see base.verify_batch_async).

    Lane routing (see the second-sight policy note above): lanes whose
    key already has a pool table — or has now been seen MIN_SIGHT times —
    ride the comb kernel (building tables as needed); the rest, plus any
    malformed lanes, verify on the f32 ladder in the same call. Both
    dispatches are enqueued before either resolves, so device work
    overlaps.

    With an open population the routing, the leases, the builds and the
    comb dispatch of one batch happen under the pool's lock (CombPool's
    docstring says why), the batch is logged there in that order, the
    ladder runs at the pool's `miss_bucket`, and a batch wider than
    OPEN_MAX_LANES is served as batches of that width."""
    n = len(items)
    if n == 0:
        return lambda: np.zeros(0, dtype=bool)
    pool_mgr = default_pool()
    if pool_mgr.open and n > OPEN_MAX_LANES:
        parts = [verify_batch_async(items[at:at + OPEN_MAX_LANES])
                 for at in range(0, n, OPEN_MAX_LANES)]
        return lambda: np.concatenate([np.asarray(r()) for r in parts])
    keys = [
        bytes(p) if len(p) == 32 and len(s) == 64 else None
        for p, _m, s in items
    ]
    # an open pool routes, leases, builds and dispatches under its lock
    with pool_mgr._lock if pool_mgr.open else contextlib.nullcontext():
        counts = _bump_seen({k for k in keys if k is not None})
        min_sight = _min_sight()
        with pool_mgr._lock:
            in_pool = {
                k for k in counts if k in pool_mgr._lru
            }
        comb_idx = [
            i
            for i, k in enumerate(keys)
            if k is not None and (k in in_pool or counts[k] >= min_sight)
        ]
        cset = set(comb_idx)
        ladder_idx = [i for i in range(n) if i not in cset]
        pool_mgr.stats["lanes_first_sight"] += sum(
            1 for i in ladder_idx if keys[i] is not None)
        resolvers: list[tuple[list[int], object]] = []
        if comb_idx:
            try:
                r = _dispatch_comb(
                    items, comb_idx, [keys[i] for i in comb_idx], pool_mgr
                )
                resolvers.append((comb_idx, r))
            except PoolExhausted:
                logger.warning(
                    "comb pool exhausted (%d lanes); ladder fallback",
                    len(comb_idx),
                )
                ladder_idx = sorted(ladder_idx + comb_idx)
                cset = set()
        if pool_mgr.open:
            pool_mgr.log_batch(keys, cset, pool_mgr.last if comb_idx else {})
    if ladder_idx:
        note(lanes_ladder=len(ladder_idx))
        sub = [items[i] for i in ladder_idx]
        r = _ladder_bucketed(sub, pool_mgr) if pool_mgr.open \
            else base.verify_batch_async(sub)
        resolvers.append((ladder_idx, r))

    def resolve():
        out = np.zeros(n, dtype=bool)
        for idx, r in resolvers:
            out[np.asarray(idx)] = np.asarray(r())
        return out

    return resolve


def _ladder_bucketed(items: list, pool_mgr: CombPool):
    """The f32 ladder over `items` in programs of the pool's `miss_bucket`
    lanes (padding repeats the first lane; its verdicts are dropped)."""
    parts = []
    for at, count in _chunks(len(items), pool_mgr.miss_bucket):
        part = items[at:at + count]
        pool_mgr.stats["ladders"] += 1
        parts.append((count, base.verify_batch_async(
            part + [part[0]] * (pool_mgr.miss_bucket - count))))
    return lambda: np.concatenate([np.asarray(r())[:c] for c, r in parts])


def compile_miss_programs(make_full) -> dict:
    """Run every program a miss can need once, at the pool's bucket, so
    that none is traced or compiled at first use: the build and the pool
    update (on the base point, into the reserved slot 0: no key becomes
    resident) and the first-sight ladder (`make_full(n)`: n lanes of
    valid signatures). Returns the seconds each took."""
    pool_mgr = default_pool()
    b = pool_mgr.miss_bucket
    out: dict[str, float] = {}
    qx = np.repeat(np.asarray(base._BX, dtype=np.float32)[:, None], b, axis=1)
    qy = np.repeat(np.asarray(base._BY, dtype=np.float32)[:, None], b, axis=1)
    with pool_mgr._lock:
        t0 = time.time()
        tables = _build_jit(jnp.asarray(qx), jnp.asarray(qy))
        tables.block_until_ready()
        out[f"build_{b}"] = round(time.time() - t0, 3)
        t0 = time.time()
        pool_mgr._pool = _update_jit(
            pool_mgr._pool, jnp.zeros(b, dtype=jnp.int32), tables)
        pool_mgr._pool.block_until_ready()
        out[f"update_{b}"] = round(time.time() - t0, 3)
    t0 = time.time()
    if not all(base.verify_batch_async(make_full(b))()):
        raise RuntimeError(f"the ladder rejected a valid lane at {b}")
    out[f"ladder_{b}"] = round(time.time() - t0, 3)
    return out


def verify_batch(items: list[tuple[bytes, bytes, bytes]]) -> np.ndarray:
    """Drop-in gateway backend (same contract as base.verify_batch)."""
    return verify_batch_async(items)()
