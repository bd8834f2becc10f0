"""Gateway kernel backend that routes batches to the device daemon.

Selected as `devd` in ops/gateway.KERNELS (and as the automatic default
when a daemon is serving — gateway.kernel_name). With this backend a
node, bench, or test process NEVER initializes a jax backend or loads
libtpu: the daemon (tendermint_tpu/devd.py) owns the device — libtpu
gives a chip to one process — and this module is pure socket IPC.

Transport policy (round 6): batches at or above TENDERMINT_DEVD_STREAM_MIN
lanes (default 256) ride the STREAMED protocol — fixed-width binary chunk
frames submitted while the daemon verifies earlier chunks, verdicts
streaming back per chunk (devd.DevdClient.verify_stream_async; protocol
in tendermint_tpu/devd.py / docs/streaming-devd.md). Below the threshold
the single-shot pickle op wins: one small frame beats stream setup. A
daemon that rejects verify_stream (version skew) latches the single-shot
path for the process lifetime.

Same contract as the kernel modules (ops/ed25519_f32.py): verify_batch
returns an array-like of bools; verify_batch_async returns a zero-arg
resolver. Failures raise — the gateway's existing CPU-fallback handling
(ops/gateway.Verifier.verify_batch) treats a dead daemon exactly like a
dead device.

Sharded plane (round 21): when TENDERMINT_DEVD_SOCKS names two or more
endpoints, every entry point delegates to ops/devd_shard — the same
contracts, dispatched across the fleet with work-stealing and
per-endpoint breakers. With one endpoint the single-client path below
runs unchanged.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from tendermint_tpu import devd

_client: devd.DevdClient | None = None
_mtx = threading.Lock()
# False once the serving daemon rejected verify_stream — don't pay a
# doomed stream attempt per batch against a pre-streaming daemon
_stream_ok = True


def _get_client() -> devd.DevdClient:
    global _client
    with _mtx:
        if _client is None:
            _client = devd.DevdClient()
        return _client


def _stream_min() -> int:
    try:
        return int(os.environ.get("TENDERMINT_DEVD_STREAM_MIN", "256"))
    except ValueError:  # a typo'd env var must not latch the CPU path
        return 256


def _use_stream(n: int) -> bool:
    return _stream_ok and n >= _stream_min()


def _shard():
    """The sharded dispatcher, when >= 2 endpoints are configured."""
    from tendermint_tpu.ops import devd_shard

    return devd_shard if devd_shard.enabled() else None


def verify_batch(items) -> np.ndarray:
    items = list(items)
    shard = _shard()
    if shard is not None:
        return np.asarray(shard.verify_batch(items), dtype=bool)
    c = _get_client()
    if _use_stream(len(items)):
        try:
            return np.asarray(c.verify_stream(items), dtype=bool)
        except devd.DevdError as exc:
            if "too old" not in str(exc):
                raise
            _latch_single_shot()
    return np.asarray(c.verify_batch(items), dtype=bool)


def verify_batch_async(items):
    items = list(items)
    shard = _shard()
    if shard is not None:
        resolve_shard = shard.verify_batch_async(items)
        return lambda: np.asarray(resolve_shard(), dtype=bool)
    c = _get_client()
    if _use_stream(len(items)):
        resolve = c.verify_stream_async(items)

        def resolve_stream() -> np.ndarray:
            try:
                return np.asarray(resolve(), dtype=bool)
            except devd.DevdError as exc:
                if "too old" not in str(exc):
                    raise
                _latch_single_shot()
                return np.asarray(c.verify_batch(items), dtype=bool)

        return resolve_stream
    resolve = c.verify_batch_async(items)
    return lambda: np.asarray(resolve(), dtype=bool)


def _latch_single_shot() -> None:
    global _stream_ok
    _stream_ok = False


def reset_stream_latches() -> None:
    """Re-arm the version-skew latches (verify, hash, AND agg planes).
    Called by the shared circuit breaker's on_close hook (ops/gateway):
    the latches are per-DAEMON facts, and a breaker re-close means the
    daemon came back — possibly upgraded — so the latched-off fast paths
    must get another chance instead of staying latched off by the build
    that died."""
    global _stream_ok, _hash_stream_ok, _agg_ok
    _stream_ok = True
    _hash_stream_ok = True
    _agg_ok = True


# -- aggregate plane ----------------------------------------------------------
#
# The aggregate-commit verify's dual-scalar-mul lanes (docs/upgrade.md):
# one "agg" op per commit, lanes batched daemon-side through
# ops/ed25519.dsm_batch. Sharded fleets split the lanes across endpoints
# with the same offset-merge per-lane attribution the verify plane has.


class AggUnsupported(Exception):
    """The serving daemon predates the agg op (version skew). The
    gateway treats this as 'route unavailable' — straight to the CPU
    floor, NO breaker penalty (the daemon is healthy, just old)."""


_agg_ok = True


def _latch_agg_off() -> None:
    global _agg_ok
    _agg_ok = False


def agg_batch(terms) -> list[tuple[int, int]]:
    """Per-lane [a]P + [b]Q over the daemon-owned device; terms as in
    ops/ed25519.dsm_batch. Raises AggUnsupported on a pre-agg daemon
    (latched for the daemon's lifetime; re-armed by breaker re-close)."""
    if not _agg_ok:
        raise AggUnsupported("daemon predates the agg op (latched)")
    terms = [tuple(t) for t in terms]
    shard = _shard()
    try:
        if shard is not None:
            return shard.agg_batch(terms)
        return _get_client().agg_batch(terms)
    except devd.DevdError as exc:
        if "unknown op" not in str(exc):
            raise
        _latch_agg_off()
        raise AggUnsupported(str(exc)) from exc


def stream_stats() -> dict:
    """Client-side streamed-transport counters; Verifier.stats() exposes
    them so the serving path is observable from the node process too.
    Sharded: summed across every endpoint's client."""
    shard = _shard()
    if shard is not None:
        return shard.stream_stats()
    return _get_client().stream_stats()


# -- hash plane ---------------------------------------------------------------
#
# Same transport policy as verify, plus a BYTES floor: part-set batches
# are few-but-fat (16 x 64 KB for a 1 MB block — far under the 256-lane
# stream min that fits signature lanes), and it is exactly those megabyte
# frames whose marshal the stream exists to overlap with device hashing.

_HASH_STREAM_MIN_BYTES = 1 << 18  # 256 KB

# the hash plane's OWN version-skew latch: a round-6 daemon serves
# verify_stream fine while rejecting hash_stream — latching the shared
# verify flag would silently reintroduce the serving-path gap PR 1 closed
_hash_stream_ok = True


def _hash_stream_min_bytes() -> int:
    try:
        return int(os.environ.get(
            "TENDERMINT_DEVD_HASH_STREAM_MIN_BYTES",
            str(_HASH_STREAM_MIN_BYTES),
        ))
    except ValueError:
        return _HASH_STREAM_MIN_BYTES


def _use_hash_stream(n: int, total_bytes: int) -> bool:
    return _hash_stream_ok and (
        n >= _stream_min() or total_bytes >= _hash_stream_min_bytes()
    )


def _latch_hash_single_shot() -> None:
    global _hash_stream_ok
    _hash_stream_ok = False


def _hash_chunk(mode: str) -> int | None:
    """Stream chunk width in ITEMS: TENDERMINT_DEVD_HASH_CHUNK pins it;
    otherwise part mode frames narrow (parts are 64 KB each — 8 parts =
    a 512 KB frame, enough to overlap decode with device compute without
    starving the pipeline), leaf mode rides the daemon-advertised verify
    width (tx leaves are sig-lane sized)."""
    try:
        env = int(os.environ.get("TENDERMINT_DEVD_HASH_CHUNK", "0") or 0)
    except ValueError:
        env = 0
    if env > 0:
        return env
    return 8 if mode == "part" else None


def hash_batch(items, mode: str = "part") -> list[bytes]:
    """Batched daemon-side hashing (gateway.Hasher's devd route):
    streamed chunk frames when the batch is wide or fat enough, the
    single-shot pickle op otherwise. Digests byte-identical to
    crypto.hashing.ripemd160 / merkle.simple.leaf_hash."""
    items = [bytes(b) for b in items]
    shard = _shard()
    if shard is not None:
        return shard.hash_batch(items, mode)
    c = _get_client()
    if _use_hash_stream(len(items), sum(len(b) for b in items)):
        try:
            return c.hash_stream(items, mode=mode, chunk=_hash_chunk(mode))
        except devd.DevdError as exc:
            if "too old" not in str(exc):
                raise
            _latch_hash_single_shot()
    return c.hash_batch(items, mode=mode)


def hash_tree(items, mode: str = "part") -> tuple[list, list]:
    """(leaf digests, postorder internal tree nodes) — the proof-free
    part-set path: one streamed pass hashes every leaf AND the whole
    Merkle tree daemon-side (merkle.simple.FlatTree.from_nodes
    rehydrates host proofs with zero host hashing). Sharded: leaves
    hash across the fleet, the internal nodes build host-side from the
    gathered digests (devd_shard.hash_tree — byte-identical buffer)."""
    items = [bytes(b) for b in items]
    shard = _shard()
    if shard is not None:
        return shard.hash_tree(items, mode)
    c = _get_client()
    if _use_hash_stream(len(items), sum(len(b) for b in items)):
        try:
            return c.hash_stream(
                items, mode=mode, tree=True, chunk=_hash_chunk(mode)
            )
        except devd.DevdError as exc:
            if "too old" not in str(exc):
                raise
            _latch_hash_single_shot()
    return c.hash_batch(items, mode=mode, tree=True)


def hash_stream_stats() -> dict:
    """Client-side hash-transport counters; gateway.Hasher.stats() folds
    them in as flat stream_* gauges for the metrics RPC. Sharded:
    summed across every endpoint's client."""
    shard = _shard()
    if shard is not None:
        return shard.hash_stream_stats()
    return _get_client().hash_stream_stats()
