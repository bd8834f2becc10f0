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

import logging
import os
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import ed25519 as ed_ref
from tendermint_tpu.devd_spans import mark
from tendermint_tpu.ops import ed25519_f32 as base

logger = logging.getLogger("ops.ed25519_comb")

P = base.P
NL = base.NL
W_POS = 64  # 4-bit windows over 256 bits
W_ENT = 16  # entries per window (digit values 0..15)
COORD_ROWS = 3 * NL  # niels coords per entry: (y-x, y+x, 2dxy), 32 limbs each


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


def _verify_comb_impl(pool, t_b, slots, r_y, r_sign, s8, h8):
    """pool: (C*W_POS*W_ENT, 96) bf16 per-validator niels tables (of -A);
    t_b: (W_POS, W_ENT, 96) f32 fixed-base table; slots: (B,) int32 pool
    slot per lane; r_y/r_sign/s8/h8 as in base._verify_impl. -> bool[B].

    Accumulates W = [s]B + [h](-A) as 128 niels lookups + 127 mixed adds
    (no doublings), then compares against R exactly like the ladder
    kernels."""
    batch = slots.shape[0]
    dh = _digits4(h8)  # (64,B) digits of h -> per-validator pool
    ds = _digits4(s8)  # (64,B) digits of s -> fixed-base table

    # [h](-A): gather 64 niels rows per lane from the pool
    pos = jnp.arange(W_POS, dtype=jnp.int32)[:, None]  # (64,1)
    flat = (slots[None, :] * W_POS + pos) * W_ENT + dh  # (64,B)
    rows_a = jnp.take(pool, flat.reshape(-1), axis=0)  # (64*B, 96) bf16
    rows_a = (
        rows_a.reshape(W_POS, batch, COORD_ROWS)
        .astype(jnp.float32)
        .transpose(0, 2, 1)
    )  # (64, 96, B)

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


def _build_tables_impl(qx, qy):
    """qx/qy: (32, n) f32 canonical affine limbs of Q = -A per validator.
    Returns (n, W_POS*W_ENT, 96) float32 niels tables (canonical limbs,
    ready for a bf16 cast).

    Structure: scan over the 64 window positions carrying Q_p = 16^p * Q;
    each step emits the 15 extended-coordinate multiples v*Q_p (v=1..15,
    a chained point_add); then one Montgomery batch inversion over all
    960 entries x n lanes normalizes to affine, and a final pass forms
    canonical niels rows. ~13 signature-verifies of device work per
    validator, amortized over every later verify of that key."""
    n = qx.shape[-1]
    zeros = qx * 0.0
    one = zeros.at[0].set(1.0)
    d2 = jnp.broadcast_to(jnp.asarray(base._D2)[:, None], (NL, n))
    q0 = (qx, qy, one, base.fmul(qx, qy))

    def pos_step(q, _):
        entries = []
        acc = q
        for _v in range(1, W_ENT):
            entries.append(jnp.stack(acc, axis=0))  # (4, 32, n)
            acc = base.point_add(acc, q, d2)
        nxt = q
        for _ in range(4):
            nxt = base.point_double(nxt)
        return nxt, jnp.stack(entries, axis=0)  # (15, 4, 32, n)

    _, ext = jax.lax.scan(pos_step, q0, None, length=W_POS)
    # ext: (64, 15, 4, 32, n) extended entries
    ext = ext.reshape(W_POS * (W_ENT - 1), 4, NL, n)
    m = ext.shape[0]  # 960

    # Montgomery batch inversion of all entry Zs: forward prefix-product
    # scan, one shared finv, backward unwind — ~2x960 fmuls instead of 960
    # full inversions.
    zs = ext[:, 2]  # (960, 32, n)

    def fwd(carry, z):
        nxt = base.fmul(carry, z)
        return nxt, carry  # prefix BEFORE this element

    total, prefix = jax.lax.scan(fwd, one, zs)
    tinv = base.finv(total)

    def bwd(carry, inp):
        z, pref = inp
        inv_z = base.fmul(carry, pref)  # carry = inv(prefix_after)
        nxt = base.fmul(carry, z)
        return nxt, inv_z

    _, zinvs_rev = jax.lax.scan(bwd, tinv, (zs[::-1], prefix[::-1]))
    zinvs = zinvs_rev[::-1]  # (960, 32, n)

    def to_niels(inp):
        entry, zinv = inp
        x = base.fmul(entry[0], zinv)
        y = base.fmul(entry[1], zinv)
        t2 = base.fmul(base.fmul(x, y), d2)
        my = base.fcanon(base.fsub(y, x))
        py = base.fcanon(base.fadd(y, x))
        t2 = base.fcanon(t2)
        return jnp.stack([my, py, t2], axis=0)  # (3, 32, n)

    niels = jax.lax.map(to_niels, (ext, zinvs))  # (960, 3, 32, n)
    niels = niels.reshape(W_POS, W_ENT - 1, COORD_ROWS, n)
    ident = jnp.zeros((W_POS, 1, COORD_ROWS, n), dtype=jnp.float32)
    ident = ident.at[:, 0, 0].set(1.0).at[:, 0, NL].set(1.0)
    full = jnp.concatenate([ident, niels], axis=1)  # (64, 16, 96, n)
    return full.transpose(3, 0, 1, 2).reshape(n, W_POS * W_ENT, COORD_ROWS)


_build_jit = jax.jit(_build_tables_impl)


def _scatter_tables(pool, slots, tables):
    return pool.at[slots].set(tables)


_scatter_jit = jax.jit(_scatter_tables)


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
    device, batched across all new keys in the request. Capacity grows by
    doubling up to `cap` (env TENDERMINT_TPU_COMB_CAP, default 12288
    slots ~= 2.4 GB bf16 — sized for the 10k-validator benchmark on a
    16 GB v5e). Eviction is LRU; the pool array is rebuilt functionally
    (no donation: an in-flight verify may still reference the old
    buffer)."""

    def __init__(self, capacity: int | None = None, max_capacity: int | None = None):
        self.cap = int(
            max_capacity
            or os.environ.get("TENDERMINT_TPU_COMB_CAP", 12288)
        )
        c0 = int(capacity or min(self.cap, 256))
        self._c = c0
        self._pool = jnp.zeros(
            (c0 * W_POS * W_ENT, COORD_ROWS), dtype=jnp.bfloat16
        )
        self._lru: OrderedDict[bytes, int] = OrderedDict()
        self._free: list[int] = list(range(c0 - 1, 0, -1))  # slot 0 reserved
        self._lock = threading.Lock()
        self._tb = jnp.asarray(b_table())
        self.stats = {"builds": 0, "build_keys": 0, "evictions": 0, "grows": 0}

    @property
    def capacity(self) -> int:
        return self._c

    def _grow(self) -> None:
        new_c = min(self._c * 2, self.cap)
        if new_c == self._c:
            return
        pad = jnp.zeros(
            ((new_c - self._c) * W_POS * W_ENT, COORD_ROWS), dtype=jnp.bfloat16
        )
        self._pool = jnp.concatenate([self._pool, pad], axis=0)
        self._free.extend(range(new_c - 1, self._c - 1, -1))
        self._c = new_c
        self.stats["grows"] += 1

    def _take_slot(self, pinned: set[int]) -> int:
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
                return slot
        raise PoolExhausted(
            f"batch needs more distinct validator keys than the comb "
            f"pool's max capacity ({self.cap} slots)"
        )

    def ensure(self, keys: list[bytes], xs: np.ndarray, ys: np.ndarray):
        """Lease slots for decompressed keys. keys[i] is the 32-byte
        compressed pubkey; xs/ys are (n, 32) u8 canonical affine limbs of
        A (NOT negated — negation happens here). Returns
        (slots int32 (n,), pool bf16 array snapshot). Caller must pass
        only keys whose decompression succeeded. Raises PoolExhausted when
        one batch holds more distinct keys than max capacity (the gateway
        backend falls back to the ladder kernel)."""
        with self._lock:
            missing: dict[bytes, int] = {}
            first_at: dict[bytes, int] = {}
            pinned: set[int] = set()
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
                        s = self._take_slot(pinned)
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
                raise
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
                tables = _build_jit(jnp.asarray(qx), jnp.asarray(qy))
                tslots = np.asarray(
                    [missing[k] for k in uniq], dtype=np.int32
                )
                # scatter whole-slot row blocks: view pool as (C, 1024, 96)
                pool3 = self._pool.reshape(self._c, W_POS * W_ENT, COORD_ROWS)
                pool3 = _scatter_jit(
                    pool3, jnp.asarray(tslots), tables.astype(jnp.bfloat16)
                )
                self._pool = pool3.reshape(
                    self._c * W_POS * W_ENT, COORD_ROWS
                )
                self.stats["builds"] += 1
                self.stats["build_keys"] += len(uniq)
            return slots, self._pool

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
    overlaps."""
    n = len(items)
    if n == 0:
        return lambda: np.zeros(0, dtype=bool)
    pool_mgr = default_pool()
    keys = [
        bytes(p) if len(p) == 32 and len(s) == 64 else None
        for p, _m, s in items
    ]
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
    if ladder_idx:
        r = base.verify_batch_async([items[i] for i in ladder_idx])
        resolvers.append((ladder_idx, r))

    def resolve():
        out = np.zeros(n, dtype=bool)
        for idx, r in resolvers:
            out[np.asarray(idx)] = np.asarray(r())
        return out

    return resolve


def verify_batch(items: list[tuple[bytes, bytes, bytes]]) -> np.ndarray:
    """Drop-in gateway backend (same contract as base.verify_batch)."""
    return verify_batch_async(items)()
