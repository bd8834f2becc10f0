"""The comb pool with an open population (ISSUE 35: more keys than
slots, so the pool misses, builds and evicts all day): the system against
plain references for seeded streams that force hits, first sights,
builds, evictions and returns of evicted keys inside one batch and across
batches; padded builds against unpadded ones, byte for byte; no program
compiled after the claim's; an in-flight batch through an eviction storm;
and the closed population's pool and the daemon's merger as they were.

Verdicts are held to crypto/ed25519.verify, routes and evictions to
perfbench/reference/pool_lru_ref.py (a dict and a counter, no device).
"""

from __future__ import annotations

import json
import os
import random
import sys

import jax.monitoring
import numpy as np
import pytest

from tendermint_tpu import devd, devd_spans
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.ops import ed25519_comb as comb

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench"))
from reference import ed25519_ref, pool_lru_ref  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SECRETS = [bytes([7, k]) + b"\x07" * 30 for k in range(96)]
PUBS = [ed.public_key(s) for s in SECRETS]
_compiles: list[str] = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **_kw: _compiles.append(event)
    if event == COMPILE_EVENT else None)


def lane(k: int, msg: bytes, forged: bool = False):
    sig = ed.sign(SECRETS[k], msg)
    if forged:
        sig = bytes([sig[0] ^ 1]) + sig[1:]
    return (PUBS[k], msg, sig)


def make_full(n: int) -> list:
    return [lane(i % 8, b"warm-%d" % i) for i in range(n)]


@pytest.fixture
def open_pool(monkeypatch):
    """A pool of 8 slots (7 keys) for an open population, MIN_SIGHT 2.
    Its miss programs are 8 wide, the pool's own size (a pool of 128 slots
    or more has them at comb.MISS_BUCKET, which tests/test_chip_compile.py
    compiles for the chip): what these tests hold is the same at any."""
    monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "2")
    comb.reset_default_pool()
    pool = comb.CombPool(max_capacity=8, open_pop=True)
    comb.set_default_pool(pool)
    yield pool
    comb.reset_default_pool()


def replay(pool, tmp_path) -> dict:
    path = pool.dump_log(str(tmp_path / "pool.jsonl"))
    with open(path) as f:
        header = json.loads(f.readline())
        return pool_lru_ref.replay(header, [json.loads(x) for x in f])


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_a_seeded_stream_against_the_references(open_pool, tmp_path, seed):
    rng = random.Random(seed)
    wrong = 0
    for step in range(40):
        # up to 7 lanes over 24 keys: every batch fits the 7 slots, most
        # evict, and a key evicted by an earlier lane can return in a later
        # lane of the same batch
        keys = [rng.randrange(24) for _ in range(rng.randint(1, 7))]
        forged = [rng.random() < 0.25 for _ in keys]
        items = [lane(k, b"s%d-%d" % (step, j), f)
                 for j, (k, f) in enumerate(zip(keys, forged))]
        oks = comb.verify_batch(items)
        wrong += sum(1 for ok, it in zip(oks, items) if bool(ok) != ed.verify(*it))
    assert wrong == 0
    out = replay(open_pool, tmp_path)
    assert out["lanes_routed_unlike_reference"] == 0
    assert out["resident_over_capacity"] == 0
    counts = out["counts"]
    assert min(counts["hit"], counts["first_sight"], counts["built"],
               counts["rebuilt"]) > 0 and out["evictions"] > 0
    s = open_pool.stats
    assert s["evictions"] == out["evictions"]
    assert s["lanes_hit"] == counts["hit"]
    assert s["lanes_first_sight"] == counts["first_sight"]
    assert s["lanes_built"] == counts["built"] + counts["rebuilt"]
    assert len(open_pool._lru) == out["resident"] <= 7


def test_an_evicted_key_returns_inside_one_batch(open_pool, tmp_path):
    for _ in range(2):                      # two sights: keys 0..6 resident
        assert all(comb.verify_batch([lane(k, b"a") for k in range(7)]))
    # key 0 is the least recently used: lane 1 (key 7, new) takes its slot,
    # lane 2 wants key 0 back in the same batch
    comb.verify_batch([lane(7, b"b")])
    oks = comb.verify_batch([lane(7, b"c"), lane(0, b"c"), lane(0, b"d", True)])
    assert list(oks) == [True, True, False]
    out = replay(open_pool, tmp_path)
    assert out["lanes_routed_unlike_reference"] == 0
    assert out["counts"]["rebuilt"] == 2 and out["evictions"] == 2


def test_padded_builds_give_the_tables_unpadded_builds_gave(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "1")
    tables = {}
    for is_open in (False, True):
        comb.reset_default_pool()
        pool = comb.CombPool(capacity=16, max_capacity=16, open_pop=is_open)
        comb.set_default_pool(pool)
        # 3 keys: one program of 3 in the closed pool, padded to 16 in the
        # open one (to 128 in a pool of 128 slots or more)
        assert all(comb.verify_batch([lane(k, b"t") for k in (3, 4, 5)]))
        assert pool._pool.shape == (16 * comb.W_POS, comb.POOL_ROW)
        arr = np.asarray(pool._pool).reshape(16, comb.W_POS, comb.POOL_ROW)
        tables[is_open] = {k: arr[pool._lru[PUBS[k]]].tobytes() for k in (3, 4, 5)}
        assert pool.stats["build_keys"] == 3
    comb.reset_default_pool()
    assert tables[False] == tables[True]
    assert len(set(tables[True].values())) == 3


def test_forty_miss_counts_compile_nothing_after_the_claim(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "2")
    comb.reset_default_pool()
    pool = comb.CombPool(max_capacity=64, open_pop=True)
    pool.miss_bucket = 8                    # 40 counts over 5 programs' worth
    comb.set_default_pool(pool)
    took = comb.compile_miss_programs(make_full)
    assert set(took) == {"build_8", "update_8", "ladder_8"}
    assert len(pool._lru) == 0              # the claim made no key resident
    for width in (8, 16, 32, 64):           # the harness warms these
        assert all(comb.verify_batch(make_full(width)))
        assert all(comb.verify_batch(make_full(width)))
    before = len(_compiles)
    at = 8
    for count in range(1, 41):              # `count` never-shown keys:
        ks = [at + (i % 88) for i in range(count)]
        at += 1
        batch = [lane(8 + (k - 8) % 88, b"m%d" % count) for k in ks]
        comb._seen.clear()                  # first sight again: the ladder
        for k in {PUBS[8 + (k - 8) % 88] for k in ks}:
            pool._lru.pop(k, None)
        pool._free = [s for s in range(63, 0, -1)
                      if s not in pool._lru.values()]
        assert all(comb.verify_batch(batch))    # `count` lanes on the ladder
        assert all(comb.verify_batch(batch))    # `count` keys built
    assert len(_compiles) == before
    assert pool.stats["ladders"] >= 40 and pool.stats["builds"] >= 40
    comb.reset_default_pool()


def test_an_in_flight_batch_survives_an_eviction_storm(open_pool):
    for _ in range(2):
        assert all(comb.verify_batch([lane(k, b"x") for k in range(6)]))
    batch = [lane(k, b"in flight %d" % k, forged=(k == 2)) for k in range(6)]
    resolve = comb.verify_batch_async(batch)        # dispatched, not read
    for step in range(6):                           # every slot retaken
        storm = [lane(10 + step * 6 + j, b"storm") for j in range(6)]
        assert all(comb.verify_batch(storm))
        assert all(comb.verify_batch(storm))
    assert not set(PUBS[:6]) & set(open_pool._lru)
    assert list(resolve()) == [True, True, False, True, True, True]


@pytest.mark.parametrize("width", [1, 8, 128])
def test_verdicts_at_a_width_through_ladder_builds_and_an_eviction_storm(
        open_pool, tmp_path, width):
    """Batches of `width` lanes over up to 7 keys: a first sight on the
    ladder, a second built and dispatched but not read while eight storms
    of new keys evict its every slot, some lanes forged in each batch.
    Every verdict is plain Ed25519's, every route the plain model's."""
    def batch(first_key: int, tag: bytes, salt: int) -> list:
        return [lane((first_key + j % 7) % len(PUBS), b"%s-%d" % (tag, j),
                     forged=(j + salt) % 3 == 0) for j in range(width)]

    a = batch(0, b"a", 0)
    served = [(a, comb.verify_batch(a))]
    held = batch(0, b"held", 1)
    resolve = comb.verify_batch_async(held)
    for step in range(1, 9):
        storm = batch(7 * step, b"storm%d" % step, step)
        served += [(storm, comb.verify_batch(storm)) for _ in range(2)]
    served.append((held, resolve()))
    assert not {it[0] for it in held} & set(open_pool._lru)
    for items, oks in served:
        assert [bool(o) for o in oks] == [ed.verify(*it) for it in items]
    assert any(not ed.verify(*it) for it in held + a)
    out = replay(open_pool, tmp_path)
    assert out["lanes_routed_unlike_reference"] == 0
    s = open_pool.stats
    assert min(s["ladders"], s["builds"], s["evictions"]) > 0


def test_a_pool_that_leaves_slots_stale_fails_the_verdicts(monkeypatch):
    """The benchmark's fault run for a stale-slot daemon, at the pool: the
    update that writes a built table into its slot does nothing."""
    monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "1")
    monkeypatch.setattr(comb, "_update_jit", lambda pool, slots, tables: pool)
    comb.reset_default_pool()
    comb.set_default_pool(comb.CombPool(max_capacity=8, open_pop=True))
    items = [lane(k, b"m") for k in range(3)]
    oks = comb.verify_batch(items)
    comb.reset_default_pool()
    assert all(ed25519_ref.verify(*it) for it in items)
    assert not any(oks)                 # every valid lane refused


def test_a_batch_wider_than_the_widest_program_is_served_in_parts(
        open_pool, monkeypatch, tmp_path):
    monkeypatch.setattr(comb, "OPEN_MAX_LANES", 8)
    assert comb.OPEN_MAX_LANES < devd.MERGE_MAX_LANES == 256
    batch = [lane(k % 5, b"w%d" % k, forged=(k == 13)) for k in range(20)]
    comb.verify_batch(batch)
    before = len(_compiles)
    oks = comb.verify_batch(batch)
    assert [bool(o) for o in oks] == [k != 13 for k in range(20)]
    assert len(_compiles) == before          # no 32-lane program was made
    with open(open_pool.dump_log(str(tmp_path / "p.jsonl"))) as f:
        sizes = [len(json.loads(x)["k"]) for x in list(f)[1:]]
    assert sizes == [8, 8, 4, 8, 8, 4]
    assert replay(open_pool, tmp_path)["lanes_routed_unlike_reference"] == 0


def test_one_miss_bucket_on_every_backend_and_a_small_pool_at_its_own_size():
    assert comb.MISS_BUCKET == 128
    assert comb.CombPool(capacity=256, open_pop=True).miss_bucket == 128
    assert comb.CombPool(max_capacity=16, open_pop=True).miss_bucket == 16
    assert list(comb._chunks(300, 128)) == [(0, 128), (128, 128), (256, 44)]
    assert list(comb._chunks(5, 128)) == [(0, 5)]


def test_the_closed_pool_and_the_merger_are_the_parents(monkeypatch):
    monkeypatch.delenv("TENDERMINT_TPU_COMB_OPEN", raising=False)
    monkeypatch.delenv("TENDERMINT_TPU_COMB_CAP", raising=False)
    monkeypatch.setenv("TENDERMINT_TPU_COMB_MIN_SIGHT", "1")
    assert (devd.MERGE_MAX_LANES, devd.MERGE_TURNS) == (256, 2)
    shipped = comb.CombPool()
    assert (shipped.open, shipped.capacity, shipped.cap) == (False, 256, 12288)
    assert shipped._log is None
    comb.reset_default_pool()
    pool = comb.CombPool(capacity=2, max_capacity=8)
    comb.set_default_pool(pool)
    held = pool._pool
    assert all(comb.verify_batch([lane(k, b"g") for k in range(5)]))
    # grown by doubling, built in one program of the exact count, and the
    # array rebuilt beside the old one (an in-flight verify may hold it)
    assert (pool.capacity, pool.stats["grows"], pool.stats["builds"]) == (8, 2, 1)
    assert np.asarray(held).shape == (2 * comb.W_POS, comb.POOL_ROW)
    assert os.environ.get("TENDERMINT_TPU_COMB_OPEN") is None
    comb.reset_default_pool()


@pytest.mark.parametrize("noted,lanes,ran", [
    ({}, 4, "all_hit"),
    ({"keys_built": 2, "slots_evicted": 2, "lanes_ladder": 1}, 4, "with_build"),
    ({"lanes_ladder": 1}, 4, "with_ladder"),
    ({"lanes_ladder": 4}, 4, "ladder_only"),
])
def test_a_call_record_says_what_it_ran(noted, lanes, ran):
    ring = devd_spans.SpanRing(size=4)
    rec = ring.begin(conn=1)
    ring.decoded(rec, "verify", lanes)
    devd_spans.note(**noted)
    devd_spans.note(build_ns=5)
    ring.finish(rec)
    row = dict(zip(devd_spans.FIELDS, ring.rows()[0]))
    assert row["ran"] == ran and row["build_ns"] == 5
    assert [row[k] for k in ("keys_built", "slots_evicted", "lanes_ladder")] == [
        noted.get(k, 0) for k in ("keys_built", "slots_evicted", "lanes_ladder")]
    devd_spans.note(keys_built=1)           # no call open: a no-op
