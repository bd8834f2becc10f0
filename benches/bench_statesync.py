"""BENCH_r10: state-sync snapshot subsystem (docs/state-sync.md).

Rows (all chip-free except the auto-appended live-daemon row):

- round-trip (ALWAYS, asserted): one producer -> restore cycle on a real
  signedkv chain, light-verified end to end, with an injected corrupt
  chunk REJECTED mid-path — the correctness gate the Makefile's
  `statesync-smoke` runs in tier 1.
- restore-vs-replay (ALWAYS, reported): cold-start cost for a fresh node
  joining an N-block signedkv chain — fast-sync-style replay (commit
  verify + execute + part hashing per height, the pre-round-10 only way
  in) vs snapshot restore (light walk to H+1 + batched chunk digests +
  wholesale apply). Restore does one commit verify per height and NO
  execution, so the gap widens with chain length / tx weight.
- sim-chunk-verify (ALWAYS, asserted >= BENCH_STATESYNC_MIN, default
  1.3x): the restore path's bulk hash workload — per-chunk RIPEMD-160
  digesting against a sim-device daemon (devd._SimHasher), streamed
  (`hash_stream`, the gateway's windowed batch-verify route) vs
  single-shot (`hash_batch`, one monolithic pickled round trip).
- live-daemon (auto-appends when a daemon already serves): the same
  chunk-verify shape against the real device, joining the live-chip
  queue (ROADMAP r06/r07 note).

BENCH_STATESYNC_SMOKE=1 shrinks sizes for the tier-1 gate; the smoke
asserts but never writes BENCH_r10.json (bench_partset's convention).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SMOKE = os.environ.get("BENCH_STATESYNC_SMOKE", "") == "1"
N_BLOCKS = int(os.environ.get("BENCH_STATESYNC_BLOCKS", "80" if SMOKE else "300"))
TXS_PER_BLOCK = int(os.environ.get("BENCH_STATESYNC_TXS", "2"))
CHUNK_SIZE = int(os.environ.get("BENCH_STATESYNC_CHUNK_BYTES", "16384"))
# the chunk-verify row keeps full size even in smoke: the streamed win
# grows with batch width, and the smoke ASSERTS the 1.3x floor. A
# 4096x1024B batch ran ~1.45x idle but dipped to 1.28x on a loaded host
# (tier-1 runs the smokes back to back) and 8192 still swung 1.34-2.5x;
# 16384 items / 1024-wide windows (bench_partset's proven shape) hold a
# tight 2.4-2.6x — fixed overheads amortize, so host noise stops
# dominating the ratio
CV_ITEMS = int(os.environ.get("BENCH_STATESYNC_CV_ITEMS", "16384"))
CV_ITEM_BYTES = int(os.environ.get("BENCH_STATESYNC_CV_ITEM_BYTES", "1024"))
CV_CHUNK = int(os.environ.get("BENCH_STATESYNC_CV_CHUNK", "1024"))
CV_TRIALS = int(os.environ.get("BENCH_STATESYNC_CV_TRIALS", "3" if SMOKE else "4"))
CV_SIM_RATE = float(os.environ.get("BENCH_STATESYNC_SIM_RATE", "1000000"))
MIN_SPEEDUP = float(os.environ.get("BENCH_STATESYNC_MIN", "1.3"))


# -- the chain both rows share ------------------------------------------------


def _build() -> tuple:
    """(chain, snap_store, manifest, chunks): an N-block signedkv chain
    with a snapshot at height N and one block past it (the manifest
    binds to header H+1)."""
    from tendermint_tpu.statesync import SnapshotProducer, SnapshotStore
    from tendermint_tpu.statesync.devchain import build_signedkv_chain

    t0 = time.perf_counter()
    chain = build_signedkv_chain(N_BLOCKS, txs_per_block=TXS_PER_BLOCK)
    build_s = time.perf_counter() - t0
    store = SnapshotStore(tempfile.mkdtemp(prefix="bench-snap-"))
    producer = SnapshotProducer(
        store, chain.app, chain.block_store, chunk_size=CHUNK_SIZE
    )
    height = producer.snapshot(chain.state)
    chain.build(1)
    manifest = store.load_manifest(height)
    chunks = [store.load_chunk(height, i) for i in range(manifest.chunks)]
    return chain, store, manifest, chunks, build_s


def _fresh_restorer(chain):
    from tendermint_tpu.abci.apps.signedkv import SignedKVStoreApp
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.rpc.light import LightClient
    from tendermint_tpu.statesync import Restorer

    lc = LightClient(
        chain.rpc_stub(), chain.genesis_doc.chain_id,
        chain.state.load_validators(1), trusted_height=0,
    )
    return Restorer(
        chain.genesis_doc, SignedKVStoreApp(), MemDB(), BlockStore(MemDB()),
        light_client=lc,
    )


# -- round-trip correctness gate ----------------------------------------------


def bench_round_trip(chain, manifest, chunks) -> dict:
    """Restore once (must succeed, byte-exact), then replay with one
    corrupt chunk injected (must be REJECTED with nothing applied)."""
    from tendermint_tpu.statesync import RestoreError

    restorer = _fresh_restorer(chain)
    t0 = time.perf_counter()
    state = restorer.restore(manifest, chunks)
    restore_s = time.perf_counter() - t0
    assert state.last_block_height == manifest.height
    assert state.app_hash == manifest.app_hash
    assert restorer.app.info().last_block_app_hash == chain.app.app_hash

    bad_restorer = _fresh_restorer(chain)
    evil = list(chunks)
    evil[len(evil) // 2] = (
        bytes([evil[len(evil) // 2][0] ^ 0x01]) + evil[len(evil) // 2][1:]
    )
    rejected = False
    try:
        bad_restorer.restore(manifest, evil)
    except RestoreError:
        rejected = True
    assert rejected, "corrupt chunk was NOT rejected"
    assert bad_restorer.app.info().last_block_height == 0, (
        "corrupt restore mutated the app"
    )
    return {
        "mode": "round-trip",
        "platform": "cpu",
        "blocks": N_BLOCKS,
        "chunks": manifest.chunks,
        "snapshot_bytes": manifest.total_bytes,
        "restore_ms": round(restore_s * 1e3, 1),
        "corrupt_chunk_rejected": rejected,
    }


# -- restore vs fast-sync replay ----------------------------------------------


def bench_restore_vs_replay(chain, manifest, chunks) -> dict:
    import threading

    from tendermint_tpu.abci.apps.signedkv import SignedKVStoreApp
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.libs.db import MemDB
    from tendermint_tpu.proxy.app_conn import AppConnConsensus
    from tendermint_tpu.state.execution import apply_block
    from tendermint_tpu.state.state import State
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.services import MockMempool

    height = manifest.height
    part_size = chain.state.params().block_gossip.block_part_size_bytes

    # -- replay: what fast sync does per height, minus the transport —
    # commit verify + part-set rebuild + execute through the app
    app = SignedKVStoreApp()
    state = State.get_state(MemDB(), chain.genesis_doc)
    store = BlockStore(MemDB())
    proxy = AppConnConsensus(LocalClient(app, threading.RLock()))
    t0 = time.perf_counter()
    for h in range(1, height + 1):
        block = chain.block_store.load_block(h)
        parts = block.make_part_set(part_size)
        commit = chain.block_store.load_block_commit(h)
        state.validators.verify_commit(
            state.chain_id, BlockID(block.hash(), parts.header()), h, commit
        )
        store.save_block(block, parts, chain.block_store.load_seen_commit(h))
        apply_block(state, None, proxy, block, parts.header(), MockMempool())
    replay_s = time.perf_counter() - t0
    assert state.last_block_height == height
    assert state.app_hash == manifest.app_hash

    # -- restore: light walk + batched chunk digests + wholesale apply
    restorer = _fresh_restorer(chain)
    t0 = time.perf_counter()
    restorer.verify_manifest(manifest)
    walk_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    restored = restorer.restore(manifest, chunks)
    apply_s = time.perf_counter() - t0
    restore_s = walk_s + apply_s
    assert restored.app_hash == state.app_hash, "restore diverged from replay"

    return {
        "mode": "restore-vs-replay",
        "platform": "cpu",
        "blocks": height,
        "txs_per_block": TXS_PER_BLOCK,
        "replay_s": round(replay_s, 3),
        "restore_s": round(restore_s, 3),
        "light_walk_s": round(walk_s, 3),
        "restore_apply_s": round(apply_s, 3),
        "speedup": round(replay_s / restore_s, 2),
        "replay_blocks_per_sec": round(height / replay_s, 1),
    }


# -- streamed vs single-shot chunk verification -------------------------------


def _spawn_daemon(extra_env: dict):
    run_dir = tempfile.mkdtemp(prefix="bench-ssd-")
    sock = os.path.join(run_dir, "devd.sock")
    env = {
        **os.environ,
        "TENDERMINT_DEVD_SOCK": sock,
        "TENDERMINT_DEVD_ACCEPT_CPU": "1",
        "TENDERMINT_DEVD_EXIT_ON_TERM": "1",
        **extra_env,
    }
    # stderr to a file: a chatty daemon on a pipe nobody drains would
    # block and hang the smoke gate (bench_partset learned this)
    err_path = os.path.join(run_dir, "daemon.err")
    with open(err_path, "wb") as err_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu.devd"],
            env=env, cwd=ROOT,
            stdout=subprocess.DEVNULL, stderr=err_f,
        )
    return proc, sock, err_path


def _wait_held(client, proc, err_path: str, deadline_s: float) -> None:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if proc.poll() is not None:
            try:
                with open(err_path, "rb") as f:
                    err = f.read()
            except OSError:
                err = b""
            raise RuntimeError(f"daemon died: {err[-2000:]!r}")
        try:
            if client.ping(timeout=2.0).get("held"):
                return
        except Exception:  # noqa: BLE001 — still starting
            pass
        time.sleep(0.5)
    raise RuntimeError("daemon never reached serving state")


def _measure_chunk_verify(client, items, chunk: int, trials: int) -> dict:
    """Digest `items` (snapshot-chunk-shaped payloads) both ways,
    best-of-`trials` each, alternated. Single-shot = one monolithic
    pickled request; streamed = the windowed chunk frames the restore
    path's batch verify rides."""
    n = len(items)
    client.hash_batch(items[: min(n, 256)])  # connection + import warm
    client.hash_stream(items[: min(n, 256)], chunk=max(chunk // 8, 32))
    single_best = stream_best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        r1 = client.hash_batch(items)
        single_best = min(single_best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        r2 = client.hash_stream(items, chunk=chunk)
        stream_best = min(stream_best, time.perf_counter() - t0)
        assert r1 == r2, "streamed digests diverge from single-shot"
    mb = sum(len(it) for it in items) / 1e6
    return {
        "chunks": n,
        "chunk_bytes": len(items[0]),
        "stream_window": chunk,
        "single_shot_mb_per_sec": round(mb / single_best, 2),
        "streamed_mb_per_sec": round(mb / stream_best, 2),
        "single_shot_ms": round(single_best * 1000, 1),
        "streamed_ms": round(stream_best * 1000, 1),
        "speedup": round(single_best / stream_best, 3),
    }


def _chunk_items() -> list[bytes]:
    return [bytes([i % 251]) * CV_ITEM_BYTES for i in range(CV_ITEMS)]


def bench_sim_chunk_verify() -> dict:
    from tendermint_tpu import devd

    proc, sock, err_path = _spawn_daemon(
        {"TENDERMINT_DEVD_SIM_RATE": str(int(CV_SIM_RATE))}
    )
    try:
        client = devd.DevdClient(sock)
        _wait_held(client, proc, err_path, 60.0)
        row = _measure_chunk_verify(client, _chunk_items(), CV_CHUNK, CV_TRIALS)
        row.update(
            mode="sim-chunk-verify", platform="sim",
            sim_device_items_per_sec=CV_SIM_RATE,
        )
        client.shutdown()
        client.close()
    finally:
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
    return row


def bench_live_daemon() -> dict | None:
    """The chunk-verify shape against an ALREADY-serving daemon — the
    live-chip row, appended whenever a daemon serves a chip."""
    from tendermint_tpu import devd

    live = devd.available(timeout=3.0)
    if live is None:
        return None
    client = devd.DevdClient()
    row = _measure_chunk_verify(
        client, _chunk_items(), CV_CHUNK, max(2, CV_TRIALS - 1)
    )
    row.update(platform=live.get("platform"), mode="live-daemon")
    client.close()
    return row


def main() -> None:
    chain, _store, manifest, chunks, build_s = _build()
    rows = [
        bench_round_trip(chain, manifest, chunks),
        bench_restore_vs_replay(chain, manifest, chunks),
    ]
    sim = bench_sim_chunk_verify()
    rows.append(sim)
    live = bench_live_daemon()
    if live is not None:
        rows.append(live)

    record = {
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "metric": (
            "statesync: restore vs fast-sync replay + streamed vs "
            "single-shot chunk verification"
        ),
        "min_speedup_asserted": MIN_SPEEDUP,
        "smoke": SMOKE,
        "chain_build_s": round(build_s, 2),
        "rows": rows,
        "note": (
            "round-trip / restore-vs-replay / sim-chunk-verify rows are "
            "chip-free; the live-daemon row auto-appends when a daemon "
            "serves (live-chip queue, ROADMAP)"
        ),
    }
    # assert BEFORE writing: a below-floor run must fail loudly without
    # replacing the recorded artifact
    assert sim["speedup"] >= MIN_SPEEDUP, (
        f"streamed chunk verify {sim['speedup']}x < {MIN_SPEEDUP}x floor"
    )
    if not SMOKE:
        with open(os.path.join(ROOT, "BENCH_r10.json"), "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")

    print(json.dumps({
        "metric": "statesync_restore_vs_replay",
        "value": rows[1]["speedup"],
        "unit": "x",
        "replay_s": rows[1]["replay_s"],
        "restore_s": rows[1]["restore_s"],
        "chunk_verify_streamed_speedup": sim["speedup"],
        "corrupt_chunk_rejected": rows[0]["corrupt_chunk_rejected"],
        "platform": "cpu+sim",
        "smoke": SMOKE,
    }))


if __name__ == "__main__":
    main()
