"""Sharded devd dispatch: N daemon endpoints behind one gateway (round 21).

One gateway -> one daemon -> one socket capped the device plane at a
single chip. This module is the dispatcher that lifts that ceiling:
``TENDERMINT_DEVD_SOCKS`` (comma-separated socket paths; the ``[device]``
config section feeds it at node assembly) names a FLEET of devd daemons,
and every verify/hash batch wide enough to shard splits into contiguous
slices scheduled across the healthy endpoints. PAPERS.md's FPGA ECDSA
verification engine (arXiv 2112.02229) is the architectural reference:
a pool of fixed-function verify engines behind one dispatch queue —
devd endpoints are that pool.

Scheduling: each dispatch plans ~2 slices per healthy endpoint (never
below the TENDERMINT_TPU_MIN_BATCH floor per slice) and gives every
slice a round-robin "home" endpoint. One worker per endpoint drains its
own slices first, then STEALS from the shared tail — so a slow chip
finishes its first slice while idle endpoints absorb the residue, and
the batch completes at the speed of the fleet, not the slowest member.

Failure semantics: each endpoint has its own ``CircuitBreaker`` in
ops/gateway's keyed registry. A failed slice records on THAT endpoint's
breaker, re-queues, and a healthy endpoint re-dispatches it — per-lane
verdict attribution survives because results merge back at the slice's
original offsets. The dispatch raises (-> the gateway's existing CPU
fallback) only when no endpoint can make progress; the plane as a whole
falls to the native/AVX floor only once every breaker is open
(gateway.devd_plane_allow).

With fewer than two endpoints ``enabled()`` is False and none of this
engages: ops/devd_backend keeps its single-client path byte-for-byte.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from tendermint_tpu import devd

logger = logging.getLogger(__name__)


class DevdShardError(devd.DevdError):
    """A sharded dispatch could not complete on ANY endpoint. The
    gateway's existing devd failure handling (bounded retry, then the
    CPU floor) treats it exactly like a dead single daemon."""


def endpoint_paths() -> list[str]:
    """The configured endpoint sockets: TENDERMINT_DEVD_SOCKS entries
    (stripped, de-duplicated, order preserved), falling back to the
    primary single socket (devd.sock_path())."""
    paths: list[str] = []
    for p in os.environ.get("TENDERMINT_DEVD_SOCKS", "").split(","):
        p = p.strip()
        if p and p not in paths:
            paths.append(p)
    if not paths:
        return [devd.sock_path()]
    return paths


def enabled() -> bool:
    """The sharded dispatcher engages only at >= 2 endpoints: with one,
    ops/devd_backend's single-client path runs unchanged."""
    return len(endpoint_paths()) >= 2


# -- endpoint objects ---------------------------------------------------------


class _Endpoint:
    """One daemon socket: its client, its version-skew latches, and its
    dispatch counters. The breaker deliberately does NOT live here — it
    sits in gateway's keyed registry so node/health, node/flightrec, and
    the telemetry scrape observe the same object the dispatcher feeds."""

    def __init__(self, path: str):
        self.path = path
        self.client = devd.DevdClient(path)
        # per-DAEMON version-skew latches (mirrors ops/devd_backend's
        # module latches): a pre-streaming daemon on one socket must not
        # latch the streamed path off for its healthy siblings
        self.stream_ok = True
        self.hash_stream_ok = True
        self.mtx = threading.Lock()
        self.outstanding = 0
        self.dispatched_slices = 0
        self.stolen_slices = 0
        self.redispatches = 0
        self.sigs = 0
        self.hash_bytes = 0
        self.sigs_per_s = 0.0  # EWMA over per-slice verify rates

    @property
    def breaker(self):
        from tendermint_tpu.ops import gateway

        return gateway.devd_breaker(self.path)

    def note_success(self, lanes: int, n_bytes: int, dt_s: float,
                     stolen: bool, sigs: bool) -> None:
        with self.mtx:
            self.dispatched_slices += 1
            if stolen:
                self.stolen_slices += 1
            if sigs:
                self.sigs += lanes
                if dt_s > 0:
                    rate = lanes / dt_s
                    self.sigs_per_s = (
                        0.8 * self.sigs_per_s + 0.2 * rate
                    ) if self.sigs_per_s else rate
            else:
                self.hash_bytes += n_bytes


_endpoints: dict[str, _Endpoint] = {}
_eps_mtx = threading.Lock()


def _fleet() -> list[_Endpoint]:
    """Endpoint objects for the CURRENT configuration, created on first
    sight (a client dials lazily, so an unreachable entry costs nothing
    until dispatched to)."""
    out = []
    with _eps_mtx:
        for path in endpoint_paths():
            ep = _endpoints.get(path)
            if ep is None:
                ep = _Endpoint(path)
                _endpoints[path] = ep
            out.append(ep)
    return out


def reset() -> None:
    """Drop the endpoint table — fresh clients and counters after env or
    socket churn (tests, benches). The breakers live in gateway's
    registry; drop those with gateway.reset_devd_breaker()."""
    with _eps_mtx:
        eps = list(_endpoints.values())
        _endpoints.clear()
    for ep in eps:
        try:
            ep.client.close()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass


def reset_endpoint_latches(path: str) -> None:
    """Re-arm one endpoint's version-skew latches (the breaker's
    on_close hook: its daemon came back, possibly upgraded)."""
    with _eps_mtx:
        ep = _endpoints.get(path)
    if ep is not None:
        ep.stream_ok = True
        ep.hash_stream_ok = True


def plane_allow() -> bool:
    """True while ANY endpoint's breaker admits work — the whole plane
    falls to the CPU floor only when every breaker is open. allow() may
    run a bounded half-open probe inline; a probe that re-closes a
    breaker makes the dispatcher's own allow() check free right after."""
    return any(ep.breaker.allow() for ep in _fleet())


# -- slicing ------------------------------------------------------------------


def _verify_floor() -> int:
    try:
        return max(1, int(os.environ.get("TENDERMINT_TPU_MIN_BATCH", "32")))
    except ValueError:  # a typo'd knob must not kill the hot path
        return 32


def _hash_floor() -> int:
    try:
        return max(1, int(
            os.environ.get("TENDERMINT_TPU_HASH_MIN_BATCH", "16")
        ))
    except ValueError:
        return 16


def _plan_slices(n: int, workers: int, floor: int) -> list[tuple[int, int]]:
    """Contiguous (start, stop) slices: ~2 per worker so there is
    residual work to steal, never more than the floor allows (each slice
    stays at or above the min-batch floor — the same width gate the
    single-socket plane applies to whole batches), never fewer than 1."""
    floor = max(1, floor)
    k = max(1, min(workers * 2, n // floor))
    base, rem = divmod(n, k)
    out, start = [], 0
    for i in range(k):
        size = base + (1 if i < rem else 0)
        out.append((start, start + size))
        start += size
    return out


def _plan_slices_weighted(
    n: int, weights: list[float], floor: int,
) -> list[tuple[int, int, int]] | None:
    """Endpoint-weighted planning (round 22, ROADMAP follow-on from
    PR 21): (start, stop, home) slices whose widths are proportional to
    each endpoint's recorded ``sigs_per_s`` EWMA, so a PERMANENTLY
    slower chip gets proportionally narrower slices up front instead of
    relying on steals every batch. Endpoints with no history yet take
    the fleet's mean recorded rate (a new chip is assumed average until
    measured). Returns None when no endpoint has history or the batch is
    too narrow to split — the caller falls back to the equal-width
    planner. Each home still gets ~2 slices when its share allows, so
    the steal tail keeps absorbing TRANSIENT slowness."""
    floor = max(1, floor)
    known = [w for w in weights if w > 0]
    if not known or n < 2 * floor:
        return None
    fill = sum(known) / len(known)
    w = [wi if wi > 0 else fill for wi in weights]
    total = sum(w)
    # largest-remainder apportionment of the n lanes over the workers
    raw = [n * wi / total for wi in w]
    shares = [int(r) for r in raw]
    short = n - sum(shares)
    for i in sorted(
        range(len(w)), key=lambda j: raw[j] - shares[j], reverse=True,
    )[:short]:
        shares[i] += 1
    out, start = [], 0
    for i, q in enumerate(shares):
        if q <= 0:
            continue
        parts = 2 if q >= 2 * floor else 1
        base, rem = divmod(q, parts)
        for j in range(parts):
            size = base + (1 if j < rem else 0)
            out.append((start, start + size, i))
            start += size
    return out or None


# -- the dispatcher -----------------------------------------------------------

# bound on full re-dispatch rounds: within a round, surviving workers
# steal a failed slice immediately; a fresh round only happens when every
# worker of the previous one exited (failed or drained), so 3 rounds is
# already "the fleet failed repeatedly" — the gateway's retry + breaker
# thresholds own anything past that
_MAX_ROUNDS = 3


def _dispatch(items: list, run, floor: int, sigs: bool) -> list:
    """Shard `items` across healthy endpoints; merge per-slice results
    back at their original offsets (per-lane attribution survives
    slicing AND re-dispatch by construction). `run(ep, sub)` executes
    one slice on one endpoint and returns len(sub) results."""
    n = len(items)
    out: list = [None] * n
    cond = threading.Condition()
    last_exc: list[BaseException] = []

    why = devd.current_why()  # the caller's, for the worker threads
    # slice records: [start, stop, home_worker_index]
    pending: list[list[int]] = []
    inflight = [0]

    for round_ in range(_MAX_ROUNDS):
        eps = [ep for ep in _fleet() if ep.breaker.allow()]
        if not eps:
            raise DevdShardError(
                "all devd endpoint breakers are open"
            ) from (last_exc[-1] if last_exc else None)
        if not pending:
            if round_ == 0:
                weighted = _plan_slices_weighted(
                    n, [ep.sigs_per_s for ep in eps], floor,
                ) if sigs else None
                if weighted is not None:
                    pending = [[s, e, h] for s, e, h in weighted]
                else:
                    pending = [
                        [s, e, i % len(eps)]
                        for i, (s, e) in enumerate(
                            _plan_slices(n, len(eps), floor)
                        )
                    ]
            else:  # everything completed in a prior round
                break
        else:
            # re-home surviving slices onto the new worker set
            for i, rec in enumerate(pending):
                rec[2] = i % len(eps)

        def take(idx: int):
            """Own-home slices first, then steal from the shared tail.
            A drained queue with slices still IN FLIGHT is not done —
            an in-flight slice may fail and re-queue, and a worker that
            exited early would strand it for a whole re-dispatch round —
            so idle workers wait for either new work or fleet idle."""
            with cond:
                while True:
                    if pending:
                        for j, rec in enumerate(pending):
                            if rec[2] == idx:
                                inflight[0] += 1
                                return pending.pop(j), False
                        inflight[0] += 1
                        return pending.pop(), True  # steal from the tail
                    if inflight[0] == 0:
                        return None, False
                    cond.wait(0.05)

        def worker(idx: int, ep: _Endpoint) -> None:
            while True:
                rec, stolen = take(idx)
                if rec is None:
                    return
                start, stop = rec[0], rec[1]
                sub = items[start:stop]
                with ep.mtx:
                    ep.outstanding += 1
                t0 = time.monotonic()
                try:
                    res = list(run(ep, sub))
                except Exception as exc:  # noqa: BLE001 — per-endpoint
                    # breaker accounting; the slice re-dispatches
                    ep.breaker.record_failure()
                    with ep.mtx:
                        ep.outstanding -= 1
                        ep.redispatches += 1
                    with cond:
                        pending.append(rec)
                        last_exc.append(exc)
                        inflight[0] -= 1
                        cond.notify_all()
                    logger.warning(
                        "devd endpoint %s failed a %d-lane slice (%s); "
                        "re-dispatching to a healthy endpoint",
                        ep.path, len(sub), exc,
                    )
                    return  # this endpoint sits out the rest of the batch
                ep.breaker.record_success()
                with ep.mtx:
                    ep.outstanding -= 1
                n_bytes = 0 if sigs else sum(len(x) for x in sub)
                ep.note_success(
                    len(sub), n_bytes, time.monotonic() - t0, stolen, sigs,
                )
                with cond:
                    out[start:stop] = res
                    inflight[0] -= 1
                    cond.notify_all()

        def asked(i: int, ep) -> None:
            with devd.asking(why):  # the caller's purpose, on this thread
                worker(i, ep)

        threads = [
            threading.Thread(
                target=asked, args=(i, ep), daemon=True,
                name=f"devd-shard-{i}",
            )
            for i, ep in enumerate(eps)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with cond:
            if not pending:
                return out
    raise DevdShardError(
        f"sharded dispatch exhausted {_MAX_ROUNDS} rounds with slices "
        "unserved"
    ) from (last_exc[-1] if last_exc else None)


# -- verify plane -------------------------------------------------------------


def _stream_min() -> int:
    from tendermint_tpu.ops import devd_backend

    return devd_backend._stream_min()


def _verify_slice(ep: _Endpoint, sub: list) -> list:
    """One verify slice on one endpoint: streamed transport at or above
    the stream floor (per-endpoint version-skew latch), single-shot
    below it — the same policy ops/devd_backend applies per batch."""
    if ep.stream_ok and len(sub) >= _stream_min():
        try:
            return list(ep.client.verify_stream(sub))
        except devd.DevdError as exc:
            if "too old" not in str(exc):
                raise
            ep.stream_ok = False
    return list(ep.client.verify_batch(sub))


def verify_batch(items) -> list[bool]:
    """Sharded verify_batch: same contract as devd_backend.verify_batch
    (per-lane bool verdicts, order preserved), fleet-wide."""
    items = list(items)
    if not items:
        return []
    return [bool(b) for b in
            _dispatch(items, _verify_slice, _verify_floor(), sigs=True)]


def verify_batch_async(items):
    """Sharded verify_batch_async: dispatch runs on a background thread
    NOW; the returned zero-arg resolver joins it. The gateway's
    _PendingBatch / prime_cache_async / pop_primed plumbing rides this
    unchanged — it only ever sees a resolver."""
    items = list(items)
    if not items:
        return lambda: []
    box: dict = {}
    evt = threading.Event()

    why = devd.current_why()

    def run() -> None:
        try:
            with devd.asking(why):
                box["res"] = verify_batch(items)
        except BaseException as exc:  # noqa: BLE001 — re-raised at resolve
            box["exc"] = exc
        finally:
            evt.set()

    threading.Thread(
        target=run, daemon=True, name="devd-shard-async"
    ).start()

    def resolve():
        evt.wait()
        if "exc" in box:
            raise box["exc"]
        return box["res"]

    return resolve


# -- aggregate plane ----------------------------------------------------------


def agg_batch(terms) -> list[tuple[int, int]]:
    """Sharded dual-scalar-mul lanes for the aggregate-commit verify
    (the 'agg' op; docs/upgrade.md): contiguous lane slices across the
    fleet, results offset-merged back — per-lane attribution survives
    slicing and re-dispatch exactly as the verify plane's does. A lane
    is one [a]P + [b]Q term, so the verify floor is the right width
    gate (each lane costs one Straus ladder, same as a signature)."""
    terms = [tuple(t) for t in terms]
    if not terms:
        return []
    return [tuple(p) for p in _dispatch(
        terms, lambda ep, sub: ep.client.agg_batch(sub),
        _verify_floor(), sigs=True,
    )]


# -- hash plane ---------------------------------------------------------------


def _hash_slice(ep: _Endpoint, sub: list, mode: str) -> list:
    """One hash slice on one endpoint: streamed chunk frames when the
    slice is wide or fat enough (per-endpoint latch), single-shot
    otherwise — devd_backend's per-batch policy, per slice."""
    from tendermint_tpu.ops import devd_backend

    total = sum(len(b) for b in sub)
    if ep.hash_stream_ok and (
        len(sub) >= devd_backend._stream_min()
        or total >= devd_backend._hash_stream_min_bytes()
    ):
        try:
            return list(ep.client.hash_stream(
                sub, mode=mode, chunk=devd_backend._hash_chunk(mode)
            ))
        except devd.DevdError as exc:
            if "too old" not in str(exc):
                raise
            ep.hash_stream_ok = False
    return list(ep.client.hash_batch(sub, mode=mode))


def hash_batch(items, mode: str = "part") -> list[bytes]:
    """Sharded hash_batch: leaf digests in order, fleet-wide."""
    items = [bytes(b) for b in items]
    if not items:
        return []
    return _dispatch(
        items, lambda ep, sub: _hash_slice(ep, sub, mode),
        _hash_floor(), sigs=False,
    )


def hash_tree(items, mode: str = "part") -> tuple[list, list]:
    """Sharded (leaf digests, postorder internal nodes). Leaf hashing —
    the expensive term (64 KB parts, tx blobs) — shards across the
    fleet; the internal tree builds host-side from the gathered digests
    with the same builder devd's hashers use
    (merkle.simple.flat_tree_from_leaf_digests), so the node buffer is
    byte-identical to a single daemon's tree frame. Internal nodes hash
    64-byte digest pairs — well under 1% of the leaf work at production
    part shapes — so a second device round trip per level would cost
    more in transport than it saves in compute."""
    digests = hash_batch(items, mode)
    from tendermint_tpu.merkle.simple import flat_tree_from_leaf_digests

    tree = flat_tree_from_leaf_digests(digests)
    return digests, tree.internal_nodes()


# -- observability ------------------------------------------------------------


def stream_stats() -> dict:
    """Verify-transport counters summed across endpoint clients (same
    key set as one DevdClient's stream_stats)."""
    return _sum_stats("stream_stats")


def hash_stream_stats() -> dict:
    """Hash-transport counters summed across endpoint clients."""
    return _sum_stats("hash_stream_stats")


def _sum_stats(method: str) -> dict:
    out: dict = {}
    for ep in _fleet():
        for k, v in getattr(ep.client, method)().items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0) + v
            else:
                out.setdefault(k, v)
    return out


def endpoint_stats() -> dict[str, dict]:
    """Per-endpoint dispatch counters + breaker state, keyed by socket
    path. node/telemetry.py exports these as the labeled
    gateway_endpoint_* families; `breaker_state` reads the registry
    breaker (0 closed / 1 half-open / 2 open) without probing it."""
    out: dict[str, dict] = {}
    for ep in _fleet():
        with ep.mtx:
            d = {
                "outstanding": ep.outstanding,
                "dispatched_slices": ep.dispatched_slices,
                "stolen_slices": ep.stolen_slices,
                "redispatches": ep.redispatches,
                "sigs": ep.sigs,
                "sigs_per_s": round(ep.sigs_per_s, 1),
                "hash_bytes": ep.hash_bytes,
            }
        d["breaker_state"] = ep.breaker.state
        out[ep.path] = d
    return out


def plane_stats() -> dict:
    """Flat fleet aggregates for the legacy metrics map (stable key set;
    in single-socket mode the dispatch counters sit at zero and `count`
    is 1 — the plane is observable either way)."""
    eps = endpoint_stats()
    vals = list(eps.values())
    return {
        "count": len(vals),
        "healthy": sum(1 for d in vals if d["breaker_state"] != 2),
        "dispatched_slices": sum(d["dispatched_slices"] for d in vals),
        "stolen_slices": sum(d["stolen_slices"] for d in vals),
        "redispatches": sum(d["redispatches"] for d in vals),
        "outstanding": sum(d["outstanding"] for d in vals),
    }
