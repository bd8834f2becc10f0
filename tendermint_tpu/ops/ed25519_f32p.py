"""Pallas TPU kernel: fp32 radix-2^8 Ed25519 verify, VMEM-resident ladder.

STATUS: the daemon's claim bakes it off against comb on a TPU; also
selectable with TENDERMINT_TPU_KERNEL=f32p.

Same field representation, bounds, and verification math as the XLA-composed
production kernel (ops/ed25519_f32.py — read its EXACTNESS ARGUMENT first;
every bound there applies unchanged here), but the entire 127-step joint
Straus ladder runs inside ONE pallas_call so intermediate limb rows never
round-trip through HBM between HLO ops. Two pallas-only wins over the
conv formulation:

- fsq uses the symmetric schoolbook (a_i*a_j counted once, doubled):
  ~528 FMAs instead of 1024. The row sums are mathematically identical to
  fmul(a, a)'s, so the f32 exactness bounds are unchanged.
- the 16-entry window-table select is an in-register masked FMA
  accumulation, not a gather through memory.

Field elements are Python lists of 32 (S, 128) float32 rows (limb-major,
fully unrolled limb arithmetic, batch in the lane dimensions).

Host marshaling is shared with ed25519_f32 (prepare_batch8); the 2-bit
digit expansion runs on-device outside the kernel (f32._digits2) so the
H2D payload stays byte-sized.

Reference hot loops this replaces: types/vote_set.go:175,
types/validator_set.go:247-250, blockchain/reactor.go:235.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops import ed25519_f32 as base

NL = base.NL  # 32 limbs of radix 2^8
R = base.R
RINV = base.RINV

_PAD_L = [float(v) for v in base._PAD]
_P_L = [float(v) for v in base._P_LIMBS]
_D2_L = [float(v) for v in base._D2]
_BX_L = [float(v) for v in base._BX]
_BY_L = [float(v) for v in base._BY]
_B2X_L = [float(v) for v in base._B2X]
_B2Y_L = [float(v) for v in base._B2Y]
_B3X_L = [float(v) for v in base._B3X]
_B3Y_L = [float(v) for v in base._B3Y]


# -- field arithmetic on lists of 32 (S, 128) f32 rows -----------------------


def _carry1_rows(x: list) -> list:
    """Parallel 1-pass carry, identical to base._carry1: hi = floor(x/256)
    moves up one limb; the top carry wraps to limb 0 with weight 38."""
    hi = [jnp.floor(x[k] * RINV) for k in range(NL)]
    out = [x[k] - hi[k] * R for k in range(NL)]
    out[0] = out[0] + 38.0 * hi[NL - 1]
    for k in range(1, NL):
        out[k] = out[k] + hi[k - 1]
    return out


def _carry3_rows(x: list) -> list:
    return _carry1_rows(_carry1_rows(_carry1_rows(x)))


def _fadd_rows(a: list, b: list) -> list:
    return _carry1_rows([a[k] + b[k] for k in range(NL)])


def _fsub_rows(a: list, b: list) -> list:
    return _carry1_rows([a[k] + _PAD_L[k] - b[k] for k in range(NL)])


def _fold_rows(acc: list) -> list:
    """acc: 63 anti-diagonal row sums; fold rows k>=32 with the hi/lo
    split from base.fmul (weight 2^(8k) = 38*2^(8(k-32)) mod p)."""
    res = list(acc[:NL])
    for k in range(NL, 2 * NL - 1):
        t = acc[k]
        t_hi = jnp.floor(t * RINV)
        t_lo = t - t_hi * R
        res[k - NL] = res[k - NL] + 38.0 * t_lo
        res[k - NL + 1] = res[k - NL + 1] + 38.0 * t_hi
    return _carry3_rows(res)


def _fmul_rows(a: list, b: list) -> list:
    acc = [None] * (2 * NL - 1)
    for i in range(NL):
        ai = a[i]
        for j in range(NL):
            p = ai * b[j]
            k = i + j
            acc[k] = p if acc[k] is None else acc[k] + p
    return _fold_rows(acc)


def _fsq_rows(a: list) -> list:
    """Symmetric schoolbook: same row sums as _fmul_rows(a, a) — the f32
    bounds hold verbatim — with ~half the FMAs."""
    acc = [None] * (2 * NL - 1)
    for i in range(NL):
        p = a[i] * a[i]
        k = 2 * i
        acc[k] = p if acc[k] is None else acc[k] + p
        for j in range(i + 1, NL):
            p2 = 2.0 * a[i] * a[j]
            k = i + j
            acc[k] = p2 if acc[k] is None else acc[k] + p2
    return _fold_rows(acc)


def _point_add_rows(p1, p2, d2):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = _fmul_rows(_fsub_rows(y1, x1), _fsub_rows(y2, x2))
    b = _fmul_rows(_fadd_rows(y1, x1), _fadd_rows(y2, x2))
    c = _fmul_rows(_fmul_rows(t1, t2), d2)
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


def _seq_carry_rows(x: list) -> list:
    carry = None
    out = []
    for k in range(NL):
        v = x[k] if carry is None else x[k] + carry
        carry = jnp.floor(v * RINV)
        out.append(v - carry * R)
    out[0] = out[0] + 38.0 * carry
    return out


def _fcanon_rows(x: list) -> list:
    """Port of base.fcanon (3 sequential passes + <=2 conditional
    p-subtractions); see its docstring for why parallel carries alone are
    not enough."""
    x = _seq_carry_rows(_seq_carry_rows(_seq_carry_rows(x)))
    for _ in range(2):
        borrow = None
        out = []
        for k in range(NL):
            v = x[k] - _P_L[k] - (borrow if borrow is not None else 0.0)
            neg = (v < 0).astype(jnp.float32)
            out.append(v + neg * R)
            borrow = neg
        ge = borrow == 0
        x = [jnp.where(ge, out[k], x[k]) for k in range(NL)]
    return x


def _finv_rows(z: list) -> list:
    def rep_sq(x, n):
        if n <= 4:
            for _ in range(n):
                x = _fsq_rows(x)
            return x

        def body(_, v):
            return jnp.stack(_fsq_rows([v[k] for k in range(NL)]))

        stacked = jax.lax.fori_loop(0, n, body, jnp.stack(x))
        return [stacked[k] for k in range(NL)]

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


# -- the kernel ---------------------------------------------------------------


def _ladder(ax_ref, ay_ref, ry_ref, rsign_ref, dig_s_ref, dig_h_ref):
    """The full f32p verify ladder — table build, 127-step joint Straus
    walk with masked-FMA select, inversion, canonicalization, R-point
    comparison. Written against ref-OR-array inputs: `x[k]` (static limb
    index) and `x[i]` (traced step index) mean the same thing for a
    pallas VMEM ref and a jnp array, so ONE body serves both the Mosaic
    kernel (_verify_kernel) and the plain-XLA per-shard path the sharded
    verifier runs on non-TPU meshes (make_sharded_verify). Returns the
    (S, LANES) int32 accept mask."""
    S, LANES = ax_ref.shape[1], ax_ref.shape[2]

    def rows(ref):
        return [ref[k] for k in range(NL)]

    def const_rows(vals):
        return [jnp.full((S, LANES), v, dtype=jnp.float32) for v in vals]

    # derive zero/one from the input rows (not jnp.zeros): under
    # shard_map the fori_loop carry must be batch-varying from step 0,
    # and a fresh constant is replicated — the scan would reject the
    # carry with a varying-manual-axes mismatch. Inside the pallas
    # kernel this is the same value either way.
    zero = ax_ref[0] * 0.0
    one_v = zero + 1.0
    zeros = [zero] * NL
    one = [one_v] + [zero] * (NL - 1)
    d2 = const_rows(_D2_L)

    ax = rows(ax_ref)
    ay = rows(ay_ref)

    def const_pt(xl, yl):
        x, y = const_rows(xl), const_rows(yl)
        return (x, y, one, _fmul_rows(x, y))

    nax = _fsub_rows(zeros, ax)
    neg_a = (nax, ay, one, _fmul_rows(nax, ay))
    na2 = _point_double_rows(neg_a)
    na3 = _point_add_rows(na2, neg_a, d2)
    ident = (zeros, one, one, zeros)
    b_row = [ident, const_pt(_BX_L, _BY_L), const_pt(_B2X_L, _B2Y_L), const_pt(_B3X_L, _B3Y_L)]
    a_row = [ident, neg_a, na2, na3]
    table = []
    for j in range(4):
        for i in range(4):
            if i == 0:
                table.append(a_row[j])
            elif j == 0:
                table.append(b_row[i])
            else:
                table.append(_point_add_rows(b_row[i], a_row[j], d2))
    def step(i, acc):
        acc = _point_double_rows(_point_double_rows(acc))
        sel = dig_s_ref[i] + 4 * dig_h_ref[i]  # (S, LANES) int32
        # masked-FMA 16-way select, accumulated row-by-row so the loop
        # carry stays a pytree of rows (no stack/unstack copies per step)
        masks = [(sel == e).astype(jnp.float32) for e in range(16)]
        addend = tuple(
            [
                sum(masks[e] * table[e][c][k] for e in range(16))
                for k in range(NL)
            ]
            for c in range(4)
        )
        res = _point_add_rows(acc, addend, d2)
        return tuple(tuple(res[c]) for c in range(4))

    acc0 = tuple(tuple(ident[c]) for c in range(4))
    acc = jax.lax.fori_loop(0, 127, step, acc0)

    px, py, pz, _ = acc
    zinv = _finv_rows(pz)
    x_aff = _fcanon_rows(_fmul_rows(px, zinv))
    y_aff = _fcanon_rows(_fmul_rows(py, zinv))
    ry = _fcanon_rows(rows(ry_ref))
    eq = jnp.ones((S, LANES), dtype=jnp.bool_)
    for k in range(NL):
        eq = eq & (y_aff[k] == ry[k])
    sign = jnp.mod(x_aff[0], 2.0).astype(jnp.int32)
    eq = eq & (sign == rsign_ref[0])
    return eq.astype(jnp.int32)


def _verify_kernel(ax_ref, ay_ref, ry_ref, rsign_ref, dig_s_ref, dig_h_ref, out_ref):
    out_ref[0] = _ladder(ax_ref, ay_ref, ry_ref, rsign_ref, dig_s_ref, dig_h_ref)


S_TILE = 8  # (8, 128) f32 rows; tile = 1024 lanes (Mosaic requires the
# second-to-last block dim divisible by 8). Window table 16*4*32 rows
# = 8.4MB VMEM; total working set fits in v5e's 16MB with the inputs.


def _make_verify(s_tile: int, interpret: bool):
    def call(ax, ay, ry, rsign, dig_s, dig_h):
        s_total = ax.shape[1]
        spec32 = pl.BlockSpec(
            (NL, s_tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
        )
        spec127 = pl.BlockSpec(
            (127, s_tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
        )
        spec1 = pl.BlockSpec(
            (1, s_tile, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
        )
        return pl.pallas_call(
            _verify_kernel,
            grid=(s_total // s_tile,),
            in_specs=[spec32, spec32, spec32, spec1, spec127, spec127],
            out_specs=spec1,
            out_shape=jax.ShapeDtypeStruct((1, s_total, 128), jnp.int32),
            interpret=interpret,
        )(ax, ay, ry, rsign, dig_s, dig_h)

    return jax.jit(call)


_verify_calls: dict = {}


def _get_verify(tile: int, interpret: bool):
    key = (tile, interpret)
    if key not in _verify_calls:
        _verify_calls[key] = _make_verify(tile, interpret)
    return _verify_calls[key]


def _interpret() -> bool:
    """Decided from the backend of the process that runs the kernel
    (ops/gateway.pallas_interpret): never from a platform guess."""
    from tendermint_tpu.ops.gateway import pallas_interpret

    return pallas_interpret()


def built_interpret_modes() -> list[bool]:
    """interpret= of every pallas_call this process has built — what the
    device daemon reports for the kernel it serves (must be [False] on
    a chip)."""
    return sorted({key[1] for key in _verify_calls})


@jax.jit
def _expand_digits(s8, h8):
    """(32, B) int32 byte limbs -> (127, S, 128) 2-bit digits MSB-first,
    computed on device so the H2D payload stays byte-shaped."""
    ds = base._digits2(s8).reshape(127, -1, 128)
    dh = base._digits2(h8).reshape(127, -1, 128)
    return ds, dh


def marshal_device_args(items: list[tuple[bytes, bytes, bytes]]):
    """Host marshal + H2D: kernel-call args for a batch. Returns
    (args, valid, n) where args feeds _get_verify(S_TILE, ...) directly.
    The SINGLE definition of the dispatch layout — verify_batch_async and
    the out-of-suite soak (scripts/check_f32.py) both use it, so a layout
    change cannot silently leave the soak measuring a stale path."""
    n = len(items)
    tile_lanes = S_TILE * 128
    # power-of-two tile counts so distinct Mosaic compiles stay bounded at
    # log2(maxN) shapes (the 127-step unrolled ladder takes ~2min to
    # compile; a fresh compile per 1024-lane band would stall consensus)
    n_tiles = 1
    while n_tiles * tile_lanes < n:
        n_tiles <<= 1
    bucket = n_tiles * tile_lanes
    ax, ay, ry, rs, s8, h8, valid = base.prepare_batch8(items, bucket)
    s_total = bucket // 128
    dig_s, dig_h = _expand_digits(jnp.asarray(s8), jnp.asarray(h8))
    args = (
        jnp.asarray(ax.reshape(NL, s_total, 128)),
        jnp.asarray(ay.reshape(NL, s_total, 128)),
        jnp.asarray(ry.reshape(NL, s_total, 128)),
        jnp.asarray(rs.reshape(1, s_total, 128)),
        dig_s,
        dig_h,
    )
    return args, valid, n


def verify_batch_async(items: list[tuple[bytes, bytes, bytes]]):
    """Marshal + enqueue now; return a zero-arg resolver for bool[B] —
    same pipelining contract as base.verify_batch_async."""
    if len(items) == 0:
        return lambda: np.zeros(0, dtype=bool)
    args, valid, n = marshal_device_args(items)
    fn = _get_verify(S_TILE, _interpret())
    ok = fn(*args)
    return lambda: materialize_verdicts(ok, valid, n)


def verify_batch(items: list[tuple[bytes, bytes, bytes]]) -> np.ndarray:
    """Drop-in gateway backend (same contract as base.verify_batch)."""
    return verify_batch_async(items)()


# -- multi-chip: the ladder sharded over a device mesh ------------------------

_sharded_calls: dict = {}


def lane_quantum(n_dev: int, on_tpu: bool) -> int:
    """Smallest lane count divisible into equal per-device shards: each
    device takes whole (S, 128) rows, and on TPU Mosaic additionally
    needs S_TILE rows per grid step."""
    return n_dev * 128 * (S_TILE if on_tpu else 1)


def make_sharded_verify(mesh, on_tpu: bool):
    """jit(shard_map(per-shard verify)) over `mesh`'s "batch" axis — the
    f32p kernel's multi-chip path.

    Pure data parallelism: all inputs are (rows, S, 128) with the S
    dimension sharded, each chip verifies its slice, no collectives
    (independent signature lanes — SURVEY §2.3). The per-shard body:

    - TPU mesh: byte-digit expansion (base._digits2, plain XLA) feeding
      the SAME Mosaic pallas_call the single-chip path runs — the
      VMEM-resident ladder, grid over the shard's tiles.
    - non-TPU mesh: the conv-lowered fp32 ladder (base._verify_impl) on
      the shard's flattened lanes. The unrolled pallas body cannot stand
      in here: it is Mosaic-shaped (~3*10^5 scalar HLO ops), and XLA CPU
      was measured at >40min compiling it (interpret mode: >9min for ONE
      128-lane tile). Same field representation, same radix-2^8 ladder
      algorithm, same accept/reject semantics (lane-for-lane parity is
      pinned by tests); the pallas BODY's own parity stays covered by the
      hardware-gated single-chip test (tests/test_ops_f32.py).

    So a CPU-mesh run (tests, dryrun_multichip) executes the f32p path's
    real sharding structure — specs, bucketing, marshal, digit layout —
    end to end, and a TPU mesh runs the real kernel per chip."""
    n_dev = mesh.size
    # Mesh is hashable by value — an id() key could hand a NEW mesh at a
    # recycled address the stale compiled call of a dead one
    key = (mesh, on_tpu)
    if key in _sharded_calls:
        return _sharded_calls[key]
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as PS

    spec = PS(None, "batch", None)

    def per_shard(ax, ay, ry, rs, s8, h8):
        s_local = ax.shape[1]
        if on_tpu:
            ds = base._digits2(s8.reshape(32, -1)).reshape(127, s_local, 128)
            dh = base._digits2(h8.reshape(32, -1)).reshape(127, s_local, 128)
            spec32 = pl.BlockSpec(
                (NL, S_TILE, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
            )
            spec127 = pl.BlockSpec(
                (127, S_TILE, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
            )
            spec1 = pl.BlockSpec(
                (1, S_TILE, 128), lambda i: (0, i, 0), memory_space=pltpu.VMEM
            )
            return pl.pallas_call(
                _verify_kernel,
                grid=(s_local // S_TILE,),
                in_specs=[spec32, spec32, spec32, spec1, spec127, spec127],
                out_specs=spec1,
                out_shape=jax.ShapeDtypeStruct((1, s_local, 128), jnp.int32),
            )(ax, ay, ry, rs, ds, dh)
        ok = base._verify_impl(
            ax.reshape(NL, -1), ay.reshape(NL, -1), ry.reshape(NL, -1),
            rs.reshape(-1), s8.reshape(32, -1), h8.reshape(32, -1),
        )
        return ok.astype(jnp.int32).reshape(1, s_local, 128)

    fn = jax.jit(
        shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec, spec, spec),
            out_specs=spec,
        )
    )
    _sharded_calls[key] = fn
    return fn


def sharded_verify_arrays(items, mesh, on_tpu: bool):
    """Marshal + dispatch a batch through make_sharded_verify, returning
    (ok_device_array, valid_mask, n) with the result STILL on device and
    sharded over the mesh — callers can inspect `.addressable_shards` to
    assert the per-device layout (dryrun_multichip does) before
    materializing. Buckets to the smallest power of two >= n that divides
    into equal per-device shards (compile count stays bounded at
    log2(maxN) shapes per mesh)."""
    n = len(items)
    if n == 0:
        return None, np.zeros(0, dtype=bool), 0
    q = lane_quantum(mesh.size, on_tpu)
    bucket = q
    while bucket < n:
        bucket <<= 1
    ax, ay, ry, rs, s8, h8, valid = base.prepare_batch8(items, bucket)
    s_total = bucket // 128
    fn = make_sharded_verify(mesh, on_tpu)
    ok = fn(
        jnp.asarray(ax.reshape(NL, s_total, 128)),
        jnp.asarray(ay.reshape(NL, s_total, 128)),
        jnp.asarray(ry.reshape(NL, s_total, 128)),
        jnp.asarray(rs.reshape(1, s_total, 128)),
        jnp.asarray(s8.reshape(32, s_total, 128)),
        jnp.asarray(h8.reshape(32, s_total, 128)),
    )
    return ok, valid, n


def materialize_verdicts(ok, valid, n: int) -> np.ndarray:
    """Fetch a device verdict array and mask to per-item booleans — the
    ONE masking tail every batched-verify exit shares (gateway sharded
    paths included), so accept/reject coercion can never drift between
    call sites."""
    if n == 0:
        return np.zeros(0, dtype=bool)
    return (np.asarray(ok).reshape(-1)[:n] != 0) & valid[:n]


def sharded_verify_batch(items, mesh, on_tpu: bool) -> np.ndarray:
    """Materialized form of sharded_verify_arrays (the gateway's entry)."""
    ok, valid, n = sharded_verify_arrays(items, mesh, on_tpu)
    return materialize_verdicts(ok, valid, n)
