"""Ask the v5e's compiler, without the chip, whether it accepts the
kernels on chip_smoke.py's path — through the program's own marshalling
shapes, at the widths the daemon warms.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2). A compile
that passes is NOT a chip run: nothing here says a word about results or
times. What it guards is that an edit to a kernel cannot be refused by
Mosaic/XLA:TPU (fast-memory limit, block shape, unaligned slice) without
tier-1 noticing — at no chip time.

One file on purpose: libtpu is loaded by the one xdist worker that gets
this file, inside a fixture, after collection. The topology is never
described at import, in a skipif, in a parametrize argument or in
conftest.py.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tendermint_tpu.crypto import ed25519 as ed

LANES = 1024  # the shape chip_smoke.py's claim warms; one f32p tile
POOL_KEYS = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def items():
    seed = b"\x07" * 32
    pub = ed.public_key(seed)
    return [(pub, b"m%d" % i, ed.sign(seed, b"m%d" % i)) for i in range(3)]


def _on(sharding, arrays):
    """Shapes of marshalled host/CPU arrays, placed on the described chip."""
    return tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        for a in arrays
    )


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.generated_code_size_in_bytes > 0
    # one v5e holds 16 GB; the program alone must leave room for the pool
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < 8 << 30, ma
    return compiled


def test_f32_ladder_compiles_for_v5e(one_chip, no_compile_cache, items):
    """ops/ed25519_f32._verify_impl — what first-sight lanes ride."""
    from tendermint_tpu.ops import ed25519_f32 as f32

    *arrays, _valid = f32.prepare_batch8(items, LANES)
    _compile(f32._verify_impl, _on(one_chip, arrays))


def test_comb_verify_compiles_for_v5e(one_chip, no_compile_cache, items):
    """ops/ed25519_comb._verify_comb_impl over a 1024-key table pool."""
    from tendermint_tpu.ops import ed25519_comb as comb
    from tendermint_tpu.ops import ed25519_f32 as f32

    pool = comb.CombPool(capacity=POOL_KEYS)
    _ax, _ay, ry, rs, s8, h8, _valid = f32.prepare_batch8(items, LANES)
    slots = jnp.zeros((LANES,), jnp.int32)
    _compile(
        comb._verify_comb_impl,
        _on(one_chip, (pool._pool, pool.table_b(), slots, ry, rs, s8, h8)),
    )


def test_comb_table_build_compiles_for_v5e(one_chip, no_compile_cache):
    """ops/ed25519_comb._build_tables_impl for a 1024-key pool's worth of
    new validators (CombPool.ensure marshals (32, n) f32 limb columns)."""
    from tendermint_tpu.ops import ed25519_comb as comb

    q = jax.ShapeDtypeStruct((comb.NL, POOL_KEYS), jnp.float32,
                             sharding=one_chip)
    _compile(comb._build_tables_impl, (q, q))


def test_f32p_pallas_ladder_compiles_for_v5e(one_chip, no_compile_cache, items):
    """ops/ed25519_f32p: the one Pallas kernel on the default claim path,
    at one tile, interpret=False — Mosaic itself. ~2 min: the long pole
    of tier-1 by design (one shape only)."""
    from tendermint_tpu.ops import ed25519_f32p as f32p

    args, _valid, _n = f32p.marshal_device_args(items)
    assert args[0].shape == (f32p.NL, LANES // 128, 128)
    compiled = _compile(
        f32p._make_verify(f32p.S_TILE, interpret=False), _on(one_chip, args)
    )
    assert "tpu_custom_call" in compiled.as_text()


def _pool_shape(comb, slots: int, sharding) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((slots * comb.W_POS, comb.POOL_ROW),
                                jnp.bfloat16, sharding=sharding)


def _entry_param(compiled, index: int) -> tuple[str, str]:
    """(name, layout) the compiled program gives its entry parameter
    `index`, as `compiled.as_text()` writes it."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    m = re.search(r"%(\S+) = \w+\[[\d,]*\](\{[^}]*\}) parameter\("
                  + str(index) + r"\)", entry)
    assert m, entry[:2000]
    return m.group(1), m.group(2)


def _relayouts_of(compiled, name: str) -> list[str]:
    """The ops of `compiled` that lay the parameter `name` out anew: a
    copy, a transpose or a reshape that is not a bitcast, of it whole."""
    return re.findall(r"= \S+ (?:copy|transpose|reshape)\(%" + re.escape(name)
                      + r"[,)]", compiled.as_text())


def test_open_pool_update_is_in_place_on_v5e(one_chip, no_compile_cache):
    """ops/ed25519_comb._update_pool_impl at the shipped ceiling (12,288
    slots, 2.4 GB) and the widest build bucket: the donated pool is the
    output's buffer, and the program needs no second pool beside it."""
    from tendermint_tpu.ops import ed25519_comb as comb

    cap, bucket = 12288, comb.MISS_BUCKET
    pool = _pool_shape(comb, cap, one_chip)
    slots = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct(
        (bucket, comb.W_POS * comb.W_ENT, comb.COORD_ROWS), jnp.float32,
        sharding=one_chip)
    compiled = jax.jit(comb._update_pool_impl, donate_argnums=(0,)).lower(
        pool, slots, tables).compile()
    ma = compiled.memory_analysis()
    pool_bytes = cap * comb.SLOT_BYTES
    assert ma.alias_size_in_bytes >= pool_bytes, ma
    assert ma.temp_size_in_bytes < pool_bytes // 8, ma
    name, layout = _entry_param(compiled, 0)
    assert layout.startswith("{1,0"), layout
    assert _relayouts_of(compiled, name) == []


@pytest.mark.parametrize("slots", [256, 12288])
@pytest.mark.parametrize("width", [8, 256])
def test_comb_program_reads_the_pool_where_it_lies_on_v5e(
        one_chip, no_compile_cache, items, slots, width):
    """_verify_comb_impl over a closed pool's 256 slots and the shipped
    ceiling's 12,288, at the narrowest and the widest width the daemon
    serves: the pool enters row-major, no program op copies it, and the
    temporaries are the gathered rows' (a (C*1024, 96) pool enters
    column-major and is copied whole on every call: 3.2 GB of
    temporaries at 12,288 slots)."""
    from tendermint_tpu.ops import ed25519_comb as comb
    from tendermint_tpu.ops import ed25519_f32 as f32

    _ax, _ay, ry, rs, s8, h8, _valid = f32.prepare_batch8(items, width)
    tb = jnp.asarray(comb.b_table())
    compiled = _compile(
        comb._verify_comb_impl,
        (_pool_shape(comb, slots, one_chip),)
        + _on(one_chip, (tb, jnp.zeros((width,), jnp.int32), ry, rs, s8, h8)))
    name, layout = _entry_param(compiled, 0)
    assert layout.startswith("{1,0"), layout
    assert _relayouts_of(compiled, name) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_open_pool_verify_and_build_compile_for_v5e(one_chip, no_compile_cache,
                                                    items):
    """The comb program over the 12,288-slot pool at the narrowest width,
    and the table build at the widest bucket, beside the pool in 16 GB."""
    from tendermint_tpu.ops import ed25519_comb as comb
    from tendermint_tpu.ops import ed25519_f32 as f32

    pool = _pool_shape(comb, 12288, one_chip)
    _ax, _ay, ry, rs, s8, h8, _valid = f32.prepare_batch8(items, 8)
    tb = jnp.asarray(comb.b_table())
    slots = jnp.zeros((8,), jnp.int32)
    _compile(comb._verify_comb_impl,
             (pool,) + _on(one_chip, (tb, slots, ry, rs, s8, h8)))
    q = jax.ShapeDtypeStruct((comb.NL, comb.MISS_BUCKET), jnp.float32,
                             sharding=one_chip)
    compiled = _compile(comb._build_tables_impl, (q, q))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
