"""The batching gateway: where the host consensus plane meets the TPU
data plane.

The reference verifies signatures one at a time, inline, at three call
sites (types/vote_set.go:175, types/validator_set.go:247,
blockchain/reactor.go:235). Here those sites call a Verifier; the gateway
decides per batch whether the TPU kernel or the CPU loop runs, with
IDENTICAL accept/reject semantics (BASELINE.md north star: byte-identical
behavior, CPU fallback below a size threshold).

Policies:
- batches below `min_tpu_batch` run on CPU (kernel launch + host marshal
  overhead beats the win for small batches; single votes stay CPU);
- direct-kernel failures (compile error, device init) permanently fall
  back to CPU — deterministic in-process failures recur per batch;
- devd-transport failures feed the shared CircuitBreaker (round 8):
  open = CPU fallback per batch, half-open ping probes on jittered
  exponential backoff restore devd routing when the daemon returns —
  a transient daemon restart never latches the process on CPU;
- `mesh` sharding: on a multi-chip jax.sharding.Mesh the batch axis is
  sharded across devices — pure data parallelism over independent
  signatures, no collectives needed in the kernel itself.
"""

from __future__ import annotations

import logging
import os
import queue
import random
import threading
import time
from collections import OrderedDict

import numpy as np

from tendermint_tpu.crypto import ed25519 as ed_cpu
from tendermint_tpu.crypto.keys import verify_any
from tendermint_tpu.devd_spans import mark as _span_mark
from tendermint_tpu.libs.envknob import env_number as _env_number

logger = logging.getLogger("ops.gateway")

Item = tuple[bytes, bytes, bytes]  # (pubkey, message, signature)


def _cpu_verify_batch(items: list[Item]) -> list[bool]:
    """CPU path: wide all-ed25519 batches ride the native C++ batch
    verifier (radix-2^51, one ctypes call — measured 1.4x the per-item
    python/OpenSSL loop; strict-RFC8032 semantics match
    crypto/ed25519.verify, parity-tested incl. high-s/bad-point edges in
    tests/test_ops_f32.py); everything else verifies per item."""
    if len(items) >= 16 and all(
        len(it[0]) == 32 and len(it[2]) == 64 for it in items
    ):
        try:
            from tendermint_tpu import native

            # ready(), not available(): the first wide batch on the live
            # vote path must never block behind a lazy C++ build
            if native.ready():
                return [bool(b) for b in native.ed25519_verify_batch(items)]
        except Exception:  # noqa: BLE001 — any native failure -> python
            logger.exception("native batch verify failed; per-item fallback")
    return [verify_any(pk, msg, sig) for pk, msg, sig in items]


# Every batch kernel exposes verify_batch(items) -> np.ndarray[bool] with
# identical accept/reject semantics (cross-checked lane-for-lane by
# tests/test_ops*.py). These are what a daemon can serve; every one
# compiles for a v5e (tests/test_chip_compile.py):
#   comb   doubling-free verify from per-validator device-resident comb
#          tables + a fixed-base comb (ops/ed25519_comb.py); first-sight
#          lanes ride the f32 ladder inside the same call
#   f32p   pallas fp32 radix-2^8, VMEM-resident ladder
#   f32    fp32 radix-2^8 depthwise-conv field mults; the one that also
#          compiles natively on the CPU backend
# The device daemon bakes comb off against f32p at claim time and serves
# the measured winner (PERF.md section 7: not settled at 1000 keys).
KERNELS = {
    "comb": "tendermint_tpu.ops.ed25519_comb",
    "f32p": "tendermint_tpu.ops.ed25519_f32p",
    "f32": "tendermint_tpu.ops.ed25519_f32",
    # not a kernel: socket IPC to the device daemon (devd.py), which runs
    # its claim-time bake-off winner (comb vs f32p on TPU; f32 on CPU) on
    # the device it holds. The automatic default whenever a daemon is
    # serving — see kernel_name().
    "devd": "tendermint_tpu.ops.devd_backend",
}


_platform_cache: dict = {}
_platform_lock = threading.Lock()

# daemon states that mean "the chip is being taken": worth waiting out.
# "failed" is final (devd.py: a claim that fails ends the daemon).
_DAEMON_PENDING = ("starting", "claiming", "warming")


def resolve_platform() -> str | None:
    """Which platform do this process's batches reach? Cached per
    process. libtpu gives a chip to ONE process, so only the chip's
    owner — the device daemon — ever initialises the accelerator; a
    process that is not the daemon is TOLD, and never dials (no
    jax.devices(), no child process) to find out. Order:

    1. TENDERMINT_TPU_PLATFORM (tests pin "cpu"; a direct-kernel
       deployment that owns its chip says "tpu");
    2. TENDERMINT_TPU_DISABLE=1 -> "cpu";
    3. the device daemon's ping: its platform once it holds the device.
       A daemon that is still claiming or warming is about to own the
       chip — wait for it, bounded by TENDERMINT_DEVD_RESOLVE_WAIT_S
       (default 600, 0 disables), rather than settle on the host path
       minutes before it serves. A daemon that reports `failed` ends
       the wait at once;
    4. otherwise None: nobody said, so this process has no accelerator
       (the Verifier then runs the host path and says so in its stats).

    The owner itself (devd after jax.devices() answered, a bench that
    runs kernels in-process) calls set_platform with what JAX reported."""
    if "v" in _platform_cache:
        return _platform_cache["v"]
    with _platform_lock:
        if "v" not in _platform_cache:
            _platform_cache["v"] = _resolve_platform_locked()
        return _platform_cache["v"]


def _resolve_platform_locked() -> str | None:
    env = os.environ.get("TENDERMINT_TPU_PLATFORM", "")
    if env:
        return env
    if os.environ.get("TENDERMINT_TPU_DISABLE", "") == "1":
        return "cpu"
    from tendermint_tpu import devd

    rep = devd.available()
    if rep is not None:
        _platform_cache["daemon_said"] = True
        return rep.get("platform")
    wait_s = float(os.environ.get("TENDERMINT_DEVD_RESOLVE_WAIT_S", "600"))
    if wait_s <= 0 or not os.path.exists(devd.sock_path()):
        return None
    deadline = time.monotonic() + wait_s
    try:
        client = devd.DevdClient(devd.sock_path())
        try:
            while time.monotonic() < deadline:
                try:
                    ping = client.ping(timeout=3.0)
                except TimeoutError:
                    # it listens and is too busy to answer (compiling, or
                    # more processes than cores): still the chip's owner
                    logger.info("device daemon slow to answer; asking again")
                    continue
                if ping.get("held"):
                    devd.bust_avail_cache()
                    _platform_cache["daemon_said"] = True
                    return ping.get("platform")
                if ping.get("status") not in _DAEMON_PENDING:
                    logger.warning(
                        "device daemon is %r (%s); no accelerator for this "
                        "process", ping.get("status"), ping.get("error"),
                    )
                    return None
                logger.info(
                    "device daemon %r; waiting for it to serve",
                    ping.get("status"),
                )
                time.sleep(min(5.0, max(0.0, deadline - time.monotonic())))
        finally:
            client.close()
    except Exception:  # noqa: BLE001 — socket died; no daemon after all
        logger.info("device daemon socket went away while resolving")
    return None


def pin_jax_cpu() -> None:
    """Force this process's jax onto the CPU backend, whatever
    JAX_PLATFORMS says. For a process that must never take the chip
    (libtpu gives it to one process, and keeps it until that process
    exits): the CPU daemon beside a real one, the multichip dry run.
    Call it BEFORE the first jax.devices()/jnp use: once a backend is
    up the update changes nothing."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def set_platform(platform: str | None) -> None:
    """Pin resolve_platform's answer for this process — for the one
    caller that KNOWS because it owns the device (the daemon, from
    jax.devices() itself)."""
    _platform_cache["v"] = platform


def on_tpu() -> bool:
    """Do this process's batches reach TPU hardware? The ONE platform
    check — the kernel default and the TPU-gated tests call this. Never
    dials: see resolve_platform."""
    return resolve_platform() == "tpu"


def pallas_interpret() -> bool:
    """interpret= for a Pallas kernel, decided from the backend of the
    process that is about to RUN it (it has initialised JAX by then, so
    asking costs nothing and cannot be wrong). A process that was told
    it is on a TPU but would build an interpreted kernel raises: the
    owner of a chip never serves the interpreter under the chip's
    name."""
    import jax

    backend = jax.default_backend()
    interpret = backend != "tpu"
    if interpret and _platform_cache.get("v") == "tpu":
        raise RuntimeError(
            f"platform resolved to 'tpu' but this process's JAX backend is "
            f"{backend!r}: refusing to build an interpreted Pallas kernel"
        )
    return interpret


def kernel_name(name: str | None = None) -> str:
    """The validated kernel choice: `name` when the caller made one (the
    daemon's claim does), else TENDERMINT_TPU_KERNEL. Raises on unknown
    names; Verifier.__init__ calls this so a typo fails at startup
    rather than silently latching the CPU fallback.

    Default is environment-aware, in priority order:
    1. a serving device daemon (devd.available) -> "devd": the daemon
       owns the chip and this process never loads libtpu at all (see
       tendermint_tpu/devd.py);
    2. real TPU hardware -> "comb" (doubling-free comb kernel; its
       first-sight lanes internally ride the f32 ladder, so a cold
       process is never worse than the f32 baseline and steady-state
       consensus batches skip all 254 doublings per signature);
    3. otherwise "f32" — the pallas kernel only runs in slow interpret
       mode on CPU backends, while the conv-composed f32 kernel compiles
       natively everywhere.
    Resolving the platform may ping a daemon, so the default branch is
    evaluated lazily here, not at import."""
    name = name or os.environ.get("TENDERMINT_TPU_KERNEL", "")
    if not name:
        from tendermint_tpu import devd

        if devd.available() is not None:
            return "devd"
        # a daemon too busy to answer that one ping within its second is
        # still the owner of the chip: resolve_platform waits it out, and
        # a platform that a daemon told us means the daemon serves (a
        # process that owns its chip directly says so in the environment)
        platform = resolve_platform()
        if _platform_cache.get("daemon_said"):
            return "devd"
        return "comb" if platform == "tpu" else "f32"
    if name not in KERNELS:
        raise ValueError(
            f"verify kernel {name!r} (TENDERMINT_TPU_KERNEL): "
            f"expected one of {sorted(KERNELS)}"
        )
    return name


def kernel_module():
    """The verify kernel the gateway runs, per TENDERMINT_TPU_KERNEL."""
    import importlib

    return importlib.import_module(KERNELS[kernel_name()])


def shard_layout(arr) -> list[tuple[int, int]]:
    """(device_id, lanes) per addressable shard of a device array, sorted
    by device — measured proof that a dispatch actually landed sharded
    (dryrun_multichip asserts it covers every mesh device evenly)."""
    try:
        return sorted(
            (s.device.id, int(np.prod(s.data.shape)))
            for s in arr.addressable_shards
        )
    except Exception:  # noqa: BLE001 — layout capture must never fail a verify
        logger.exception("shard layout capture failed")
        return []


def _split_by_key_type(items: list[Item]):
    """(ed25519 items, their positions, other items, their positions).
    The kernel is ed25519-only; secp256k1 (33-byte pubkeys) and anything
    malformed verify on CPU (crypto/secp256k1.py explains why ECDSA
    stays off the device)."""
    ed_items, ed_pos, other_items, other_pos = [], [], [], []
    for i, it in enumerate(items):
        if len(it[0]) == 32 and len(it[2]) == 64:
            ed_items.append(it)
            ed_pos.append(i)
        else:
            other_items.append(it)
            other_pos.append(i)
    return ed_items, ed_pos, other_items, other_pos


class CircuitBreaker:
    """Shared closed → open → half-open degradation/recovery policy for
    the devd device plane (round 8).

    Before this existed, every consumer latched its own one-way flag on
    failure: `Verifier._demote_after_failure` pinned the process to the
    CPU fallback FOREVER after 3 transport errors, and the hash plane
    kept a separate single-shot skew latch — so a 2-second daemon
    restart demoted a live consensus node to CPU for its whole lifetime.
    The breaker replaces all of that with one recoverable state machine
    shared by both planes (Verifier, Hasher, ShardedVerifier's inherited
    paths, the mempool SigBatcher and consensus prime_cache_async, which
    all dispatch through them):

    - CLOSED: devd routes normally. `threshold` CONSECUTIVE failures
      (default 3, TENDERMINT_TPU_BREAKER_FAILURES) open it.
    - OPEN: callers route to the CPU fallback per batch — verdicts and
      digests stay correct, only the transport degrades. Probes are
      scheduled on exponential backoff with jitter (base
      TENDERMINT_TPU_BREAKER_BACKOFF_S, default 0.5 s; cap
      TENDERMINT_TPU_BREAKER_BACKOFF_CAP_S, default 30 s).
    - HALF-OPEN: when a probe is due, `allow()` runs it inline — the
      existing devd ping (cheap, ~1 ms against a live daemon, bounded
      ~1 s against a dead one; at most one caller probes per window,
      concurrent callers stay on the fallback). A healthy probe
      re-CLOSES the breaker and devd routing resumes; a failed one
      re-opens with doubled backoff. With no probe injected, the one
      `allow()` that finds a due window returns True as a TRIAL request
      and its record_success/record_failure settles the state.

    Observability: `stats()` returns flat numeric gauges (state,
    open/close transition counts, probe counts, consecutive failures,
    cumulative seconds on the fallback) that Verifier/Hasher `stats()`
    fold in — the metrics RPC exports them, so operators SEE
    degradation instead of inferring it from throughput."""

    CLOSED, HALF_OPEN, OPEN = 0, 1, 2

    def __init__(self, threshold: int | None = None,
                 base_backoff_s: float | None = None,
                 max_backoff_s: float | None = None,
                 probe=None, on_close=None, seed: int | None = None):
        self.threshold = max(1, int(
            threshold if threshold is not None
            else _env_number("TENDERMINT_TPU_BREAKER_FAILURES", 3)
        ))
        self.base_backoff_s = float(
            base_backoff_s if base_backoff_s is not None
            else _env_number("TENDERMINT_TPU_BREAKER_BACKOFF_S", 0.5)
        )
        self.max_backoff_s = float(
            max_backoff_s if max_backoff_s is not None
            else _env_number("TENDERMINT_TPU_BREAKER_BACKOFF_CAP_S", 30.0)
        )
        self._probe = probe
        self._on_close = on_close
        self._rng = random.Random(seed)
        self._mtx = threading.Lock()
        self._state = self.CLOSED
        self._fails = 0
        self._backoff = self.base_backoff_s
        self._opened_at = 0.0
        self._next_probe = 0.0
        self._probing = False
        self._opens = 0
        self._closes = 0
        self._probes = 0
        self._probe_failures = 0
        self._fallback_s = 0.0

    def _jittered(self, backoff: float) -> float:
        # full jitter on [0.5x, 1.5x]: many processes sharing one daemon
        # must not probe in lockstep after a restart
        return backoff * (0.5 + self._rng.random())

    def _open_locked(self, now: float, *, reopen: bool) -> None:
        if self._state != self.OPEN and not reopen:
            self._opens += 1
            self._opened_at = now
            self._backoff = self.base_backoff_s
        self._state = self.OPEN
        if reopen:
            self._backoff = min(self._backoff * 2.0, self.max_backoff_s)
        self._next_probe = now + self._jittered(self._backoff)

    def _close_locked(self, now: float) -> None:
        if self._state != self.CLOSED:
            self._closes += 1
            self._fallback_s += now - self._opened_at
        self._state = self.CLOSED
        self._fails = 0
        self._backoff = self.base_backoff_s

    def allow(self) -> bool:
        """May the caller route to devd right now? CLOSED: yes. OPEN
        with a probe due: run the probe (or admit one trial request) —
        success restores routing for everyone. Otherwise: no, take the
        fallback."""
        with self._mtx:
            if self._state == self.CLOSED:
                return True
            now = time.monotonic()
            if self._probing or now < self._next_probe:
                return False
            self._state = self.HALF_OPEN
            self._probes += 1
            if self._probe is None:
                # trial mode: this one request IS the probe; its
                # record_success/record_failure settles the state.
                # Advance the window NOW so concurrent/subsequent
                # callers stay on the fallback while the trial is in
                # flight (at most one trial per window — the same
                # contract the inline-probe branch keeps via _probing)
                self._next_probe = time.monotonic() + self._jittered(
                    self._backoff
                )
                return True
            self._probing = True
            probe = self._probe
        ok = False
        try:
            ok = bool(probe())
        except Exception:  # noqa: BLE001 — a raising probe is a failed probe
            logger.exception("breaker probe raised")
        closed = False
        with self._mtx:
            self._probing = False
            now = time.monotonic()
            if ok:
                self._close_locked(now)
                closed = True
            else:
                self._probe_failures += 1
                # reopen ONLY if this probe still owns the half-open
                # slot: a concurrent record_success may have closed the
                # breaker while the probe ran, and that fresh success
                # evidence outranks the stale probe verdict (reopening
                # a CLOSED breaker here would also leave _opened_at
                # pointing at the previous episode, double-counting
                # fallback_s on the next close)
                if self._state == self.HALF_OPEN:
                    self._open_locked(now, reopen=True)
        if closed:
            logger.warning("devd breaker re-closed: device routing restored")
            self._run_on_close()
        return ok

    def record_success(self) -> None:
        closed = False
        with self._mtx:
            self._fails = 0
            if self._state != self.CLOSED:
                self._close_locked(time.monotonic())
                closed = True
        if closed:
            logger.warning("devd breaker re-closed: device routing restored")
            self._run_on_close()

    def record_failure(self) -> bool:
        """Note one failure; True if the breaker is now open."""
        with self._mtx:
            now = time.monotonic()
            self._fails += 1
            if self._state == self.HALF_OPEN:
                # the trial request failed: straight back to OPEN with
                # doubled backoff
                self._probe_failures += 1
                self._open_locked(now, reopen=True)
                return True
            if self._state == self.CLOSED and self._fails >= self.threshold:
                self._open_locked(now, reopen=False)
                logger.warning(
                    "devd breaker OPEN after %d consecutive failures; "
                    "CPU fallback until a probe finds the daemon healthy",
                    self._fails,
                )
                return True
            return self._state == self.OPEN

    def _run_on_close(self) -> None:
        if self._on_close is None:
            return
        try:
            self._on_close()
        except Exception:  # noqa: BLE001 — a bad hook must not block recovery
            logger.exception("breaker on_close hook failed")

    @property
    def state(self) -> int:
        with self._mtx:
            return self._state

    def stats(self) -> dict:
        with self._mtx:
            now = time.monotonic()
            current = (now - self._opened_at) if self._state != self.CLOSED \
                else 0.0
            return {
                "breaker_state": self._state,  # 0 closed/1 half-open/2 open
                "breaker_opens": self._opens,
                "breaker_closes": self._closes,
                "breaker_probes": self._probes,
                "breaker_probe_failures": self._probe_failures,
                "breaker_consecutive_failures": self._fails,
                "breaker_fallback_s": round(self._fallback_s + current, 3),
            }


_devd_breakers: dict[str, CircuitBreaker] = {}
_breaker_mtx = threading.Lock()


def _devd_probe(path: str | None = None) -> bool:
    """The breaker's half-open health probe: ONE fresh ping (never the
    TTL cache — it may predate the daemon's death) proving a daemon is
    serving AND holds the device. `path` probes one sharded-plane
    endpoint; default is the primary socket."""
    from tendermint_tpu import devd

    devd.bust_avail_cache(path)
    return devd.available(timeout=1.0, path=path) is not None


def devd_breaker(endpoint: str | None = None) -> CircuitBreaker:
    """The breaker for one devd endpoint, from the keyed registry
    (round 21: the sharded device plane holds one breaker PER daemon
    socket, so a sick chip degrades capacity instead of the node).

    The no-arg form is the pre-sharding contract every existing consumer
    keeps using — Verifier, Hasher, node/health, node/flightrec,
    node/telemetry: it returns the PRIMARY endpoint's breaker (the first
    configured socket — with one daemon, the only one), so single-socket
    deployments still share ONE degradation state and recovery restores
    every plane at once."""
    if endpoint is None:
        from tendermint_tpu import devd

        endpoint = devd.sock_path()
    with _breaker_mtx:
        br = _devd_breakers.get(endpoint)
        if br is None:
            br = CircuitBreaker(
                probe=lambda: _devd_probe(endpoint),
                # a re-close means the daemon came BACK — possibly a
                # different build, so the per-daemon version-skew
                # latches must re-learn (devd_backend docstring)
                on_close=lambda: _breaker_on_close(endpoint),
            )
            _devd_breakers[endpoint] = br
        return br


def _breaker_on_close(endpoint: str) -> None:
    """Re-arm the version-skew latches for the endpoint whose breaker
    just re-closed: the single-socket client's module latches when it is
    the primary socket, and the sharded plane's per-endpoint latches
    either way."""
    from tendermint_tpu import devd
    from tendermint_tpu.ops import devd_backend, devd_shard

    devd_shard.reset_endpoint_latches(endpoint)
    if endpoint == devd.sock_path():
        devd_backend.reset_stream_latches()


def devd_breaker_states() -> dict[str, int]:
    """Snapshot of every REGISTERED breaker's state, keyed by endpoint
    socket path (never instantiates one — a scrape/watchdog must not
    spawn breakers for endpoints nothing has dispatched to)."""
    with _breaker_mtx:
        items = list(_devd_breakers.items())
    return {path: br.state for path, br in items}


def reset_devd_breaker() -> None:
    """Drop every registered breaker (tests; also re-reads the env
    knobs)."""
    with _breaker_mtx:
        _devd_breakers.clear()


# -- devd plane gating (round 21) --------------------------------------------
#
# Verifier/Hasher route per BATCH through these instead of the raw
# breaker: with one endpoint they ARE the one breaker (byte-for-byte the
# pre-sharding behavior); with N endpoints the plane admits work while
# ANY endpoint's breaker does, the dispatcher (ops/devd_shard) does the
# per-endpoint accounting slice by slice, and the CPU floor engages only
# when every breaker is open.


def devd_plane_allow() -> bool:
    """Admission gate for the devd route as a whole."""
    from tendermint_tpu.ops import devd_shard

    if devd_shard.enabled():
        return devd_shard.plane_allow()
    return devd_breaker().allow()


def devd_plane_failure() -> None:
    """A devd-route batch raised. Single-socket: count it on the one
    breaker. Sharded: the dispatcher already recorded each slice failure
    on the endpoint that failed it — a plane-level raise means no
    healthy endpoint remained, which those breakers already show, so
    recording it again (on the primary) would double-count."""
    from tendermint_tpu.ops import devd_shard

    if not devd_shard.enabled():
        devd_breaker().record_failure()


def devd_plane_success() -> None:
    """Mirror of devd_plane_failure for the success path."""
    from tendermint_tpu.ops import devd_shard

    if not devd_shard.enabled():
        devd_breaker().record_success()


class _PendingBatch:
    """An in-flight prime_cache_async dispatch. Each primed item maps to
    the shared handle; a background thread materializes the verdicts the
    moment the device answers — so the transport is ALWAYS drained (a
    devd stream whose resolver never ran would strand its connection and
    the daemon's sender), even when no verify_one ever pops an item
    (FIFO eviction, re-primed duplicates). result_for just waits.
    `on_done(dt_s)` fires once on successful resolution with the
    dispatch→verdicts wall time (the round-16 vote plane's batch
    histogram rides it)."""

    __slots__ = ("_done", "_event", "_ipc_ns")

    def __init__(self, items: list[Item], resolve, on_done=None):
        self._done: dict[Item, bool] = {}
        self._event = threading.Event()
        self._ipc_ns = 0
        t0 = time.monotonic()

        def materialize() -> None:
            from tendermint_tpu import devd

            ipc0 = devd.thread_ipc_ns()
            try:
                verdicts = resolve()
                # what the batch's round trip spent outside the daemon
                # (the devd client measures it on the resolving thread)
                self._ipc_ns = devd.thread_ipc_ns() - ipc0
                self._done.update(
                    (it, bool(ok)) for it, ok in zip(items, verdicts)
                )
                if on_done is not None:
                    on_done(time.monotonic() - t0)
            except Exception:  # noqa: BLE001 — round-8 latch sweep:
                # genuinely unconditional, NOT breaker business. The
                # resolver underneath already did the breaker accounting
                # (Verifier.verify_batch_async's resolve demotes through
                # _demote_after_failure); anything that still escapes
                # here only UNPRIMES the items — verify_one re-verifies
                # each on CPU, so a lost batch is latency, never a wrong
                # or dropped verdict (idempotent merge)
                logger.exception("async prime resolve failed")
            finally:
                self._event.set()

        threading.Thread(
            target=materialize, daemon=True, name="gateway-prime"
        ).start()

    def result_for(self, item: Item) -> bool | None:
        """The primed verdict, or None if the batch failed to resolve
        (caller re-verifies on CPU — never reject on transport loss)."""
        self._event.wait()
        return self._done.get(item)

    def take_ipc_ns(self) -> int:
        """The batch's IPC, once: the first caller to pop a lane of it
        books it (a batch is one round trip, however many lanes)."""
        ns, self._ipc_ns = self._ipc_ns, 0
        return ns


class Verifier:
    """Batch signature verifier with TPU acceleration and CPU fallback."""

    def __init__(self, min_tpu_batch: int | None = None,
                 use_tpu: bool | None = None, host_fallback: bool = True,
                 kernel: str | None = None):
        # kernel: a name of KERNELS the caller chose (the daemon's claim
        # passes each candidate); None reads TENDERMINT_TPU_KERNEL, then
        # the default of kernel_name().
        # host_fallback=False is the device daemon's own verifier: the
        # owner of the chip never answers an Ed25519 lane from the host.
        # A kernel that raises there propagates (an error frame to the
        # client, whose breaker then does its job) instead of latching
        # this verifier onto the host path under the device's name.
        self._host_fallback = host_fallback
        if min_tpu_batch is None:
            # operator knob (round 8): small-validator-set deployments
            # (localnet, chaos harnesses) route narrow consensus batches
            # through devd only when told to
            min_tpu_batch = int(
                _env_number("TENDERMINT_TPU_MIN_BATCH", 32, cast=int)
            )
        chosen = kernel or os.environ.get("TENDERMINT_TPU_KERNEL", "")
        kernel = None
        if use_tpu is None:
            if os.environ.get("TENDERMINT_TPU_DISABLE", "") == "1":
                use_tpu = False
            else:
                # default policy: the kernel path needs an accelerator (a
                # serving daemon or real hardware) or an explicit operator
                # kernel choice — on a CPU-only host the f32 kernel is
                # SLOWER than the native C++ batch verifier the CPU path
                # runs (measured: ~5k vs ~10k sigs/s), so "no accelerator"
                # must mean the native path, not a de-optimizing kernel
                kernel = kernel_name(chosen)
                use_tpu = kernel == "devd" or bool(chosen) or on_tpu()
        if kernel is None and use_tpu:
            kernel = kernel_name(chosen)
        # kernel choice is resolved ONCE per verifier (a typo'd env var
        # fails at startup; a daemon appearing or dying mid-run cannot
        # flip the hot path under a live consensus node)
        self._kernel = kernel if use_tpu else None
        if self._kernel is not None and self._kernel != "devd":
            # this process compiles: the persistent cache must be on
            # before its first jit (jitcache.enable is idempotent)
            from tendermint_tpu.jitcache import enable as _enable_cache

            _enable_cache()
        self.min_tpu_batch = min_tpu_batch
        self._tpu_ok = use_tpu
        self._mtx = threading.Lock()
        self._stats = {
            "tpu_batches": 0, "tpu_sigs": 0, "cpu_sigs": 0,
            # of cpu_sigs, the one-signature verifies (verify_one: a lone
            # vote, a proposal): the host by design, never a fallback
            "single_sigs": 0,
            # aggregate-commit verify lanes (docs/upgrade.md): device-
            # batched dual-scalar-muls vs the pure-python CPU floor
            "agg_batches": 0, "agg_lanes_device": 0, "agg_lanes_cpu": 0,
        }
        # verify-ahead results for the live vote path: consensus drains a
        # run of queued votes, batch-verifies here, then each add_vote's
        # verify_one pops its primed result (single-use)
        self._primed: dict[Item, bool] = {}
        self._primed_cap = 1 << 14

    def _kernel_module(self):
        """The batch kernel this verifier dispatches to. Overridable so
        ShardedVerifier can pin f32 for BOTH the sync and async paths."""
        import importlib

        return importlib.import_module(KERNELS[self._kernel])

    def _demote_after_failure(self) -> None:
        """A verify raised.

        devd route: feed the SHARED circuit breaker (round 8; replaces
        the permanent `_devd_fails >= 3 -> CPU forever` latch and the
        devd -> direct-kernel demotion). While the breaker is closed the
        caller's retry re-dispatches over devd (bounded: each failure
        counts toward the open threshold); once open, `_use_device`
        routes to the CPU fallback per batch and the breaker's ping
        probes restore devd routing when the daemon returns — a
        transient daemon restart costs seconds of fallback, not the
        process lifetime. The old dead-daemon -> in-process direct
        kernel switch is deliberately GONE: it was one-way (the daemon
        coming back found this process holding the chip — the one-owner
        violation devd exists to prevent) and its platform re-resolve
        could block the verify hot path behind a 45 s subprocess probe.
        A daemon retired FOR GOOD is an operator topology change: restart
        the node or set TENDERMINT_TPU_KERNEL explicitly.

        Direct-kernel failures still latch CPU permanently — a compile
        or device-init error in THIS process is deterministic, so
        retrying it per batch would fail identically (annotated per the
        round-8 latch sweep)."""
        if self._kernel == "devd":
            devd_plane_failure()
            return
        self._tpu_ok = False

    def _use_device(self, n: int) -> bool:
        """Route this batch to the kernel path? Size/health gates plus,
        on the devd route, the breaker plane (every breaker OPEN means
        CPU fallback for this batch — never a permanent demotion)."""
        if not (self._tpu_ok and n >= self.min_tpu_batch):
            return False
        return self._kernel != "devd" or devd_plane_allow()

    def _note_device_success(self) -> None:
        if self._kernel == "devd":
            devd_plane_success()

    # -- core API ----------------------------------------------------------

    def verify_batch(self, items: list[Item], _attempt: int = 0) -> list[bool]:
        n = len(items)
        if n == 0:
            return []
        ed_items, ed_pos, other_items, other_pos = _split_by_key_type(items)
        if other_items and ed_items:
            # mixed key types: kernel for the ed25519 lanes, CPU for the
            # rest, results re-interleaved in order
            out: list = [None] * n
            for p, ok in zip(ed_pos, self.verify_batch(ed_items)):
                out[p] = ok
            for p, ok in zip(other_pos, _cpu_verify_batch(other_items)):
                out[p] = ok
            with self._mtx:
                self._stats["cpu_sigs"] += len(other_items)
            return out
        if other_items:  # nothing for the kernel at all
            with self._mtx:
                self._stats["cpu_sigs"] += n
            return _cpu_verify_batch(items)
        if self._use_device(n) and _attempt <= self._max_retries():
            try:
                ops_ed = self._kernel_module()

                out = ops_ed.verify_batch(items)
                with self._mtx:
                    self._stats["tpu_batches"] += 1
                    self._stats["tpu_sigs"] += n
                self._note_device_success()
                return [bool(b) for b in out]
            except Exception:
                logger.exception("batch verify via %s failed", self._kernel)
                if not self._host_fallback:
                    raise
                self._demote_after_failure()
                # at-least-once with idempotent merge: the WHOLE batch
                # re-verifies (devd retry while the breaker stays closed,
                # else the CPU fallback) — a chunk whose stream died
                # mid-flight is re-dispatched, never dropped. _attempt
                # bounds THIS batch's retries even when concurrent
                # successes on the other plane keep resetting the shared
                # breaker's consecutive-failure count (the recursion
                # must never be open-ended on the consensus hot path)
                return self.verify_batch(items, _attempt=_attempt + 1)
        if not self._host_fallback:
            raise RuntimeError(
                f"device-only verifier has no device route for {n} lanes "
                f"(kernel {self._kernel!r})"
            )
        with self._mtx:
            self._stats["cpu_sigs"] += n
        return _cpu_verify_batch(items)

    def _max_retries(self) -> int:
        """Per-BATCH retry bound for the devd route (direct kernels
        never retry: their failures latch). Matches the breaker
        threshold so a lone caller still drives the breaker open before
        giving up, while a batch can never recurse past it."""
        if self._kernel != "devd":
            return 0
        return devd_breaker().threshold

    def verify_batch_async(self, items: list[Item], _attempt: int = 0):
        """Pipelined form of verify_batch: marshals + enqueues the device
        kernel now, returns a zero-arg resolver that blocks for results.
        Host marshaling of the next batch can overlap device execution of
        this one (jax async dispatch). Falls back to an already-resolved
        CPU result below the batch threshold or after a TPU failure."""
        n = len(items)
        if n == 0:
            return lambda: []
        ed_items, ed_pos, other_items, other_pos = _split_by_key_type(items)
        if other_items:
            inner = self.verify_batch_async(ed_items) if ed_items else (lambda: [])
            with self._mtx:
                self._stats["cpu_sigs"] += len(other_items)

            def resolve_mixed():
                out: list = [None] * n
                for p, ok in zip(ed_pos, inner()):
                    out[p] = bool(ok)
                for p, ok in zip(other_pos, _cpu_verify_batch(other_items)):
                    out[p] = ok
                return out

            return resolve_mixed
        if self._use_device(n) and _attempt <= self._max_retries():
            try:
                ops_ed = self._kernel_module()
                if not hasattr(ops_ed, "verify_batch_async"):
                    # a kernel without a pipelined entry point verifies
                    # synchronously under the same contract
                    res_now = self.verify_batch(items)
                    return lambda: res_now

                kernel_resolve = ops_ed.verify_batch_async(items)
                # the daemon's per-call record: a kernel that marks its own
                # phases has ended these two already (the first mark wins);
                # for one that does not, the pipelined path still shows
                # where the enqueue ends and the wait for verdicts does
                _span_mark("dispatch")
                with self._mtx:
                    self._stats["tpu_batches"] += 1
                    self._stats["tpu_sigs"] += n

                def resolve():
                    # async dispatch surfaces device-side failures only at
                    # materialization: keep the sync path's fallback
                    # guarantee here too.
                    try:
                        out = kernel_resolve()
                        _span_mark("device_wait")
                        res = [bool(b) for b in out]
                        self._note_device_success()
                        return res
                    except Exception:
                        logger.exception(
                            "verify via %s failed at resolve", self._kernel
                        )
                        with self._mtx:
                            self._stats["tpu_batches"] -= 1
                            self._stats["tpu_sigs"] -= n
                        if not self._host_fallback:
                            raise
                        self._demote_after_failure()
                        return self.verify_batch(items)

                return resolve
            except Exception:
                logger.exception("batch verify via %s failed", self._kernel)
                if not self._host_fallback:
                    raise
                self._demote_after_failure()
                return self.verify_batch_async(items, _attempt=_attempt + 1)
        if not self._host_fallback:
            raise RuntimeError(
                f"device-only verifier has no device route for {n} lanes "
                f"(kernel {self._kernel!r})"
            )
        with self._mtx:
            self._stats["cpu_sigs"] += n
        res = _cpu_verify_batch(items)
        return lambda: res

    def verify_aggregate(self, pubs: list[bytes], msgs: list[bytes],
                         rs: list[bytes], s_agg: bytes,
                         _attempt: int = 0) -> bool:
        """Half-aggregate verify (crypto/ed25519_agg equation) with the
        n+1 dual-scalar-mul lanes batched through the device plane —
        devd 'agg' op (sharded fleets slice the lanes with per-lane
        attribution), or the in-process int32 dsm ladder on a direct
        kernel. The pure-python reference (~4.5 ms/lane) is the CPU
        floor, taken below min_tpu_batch lanes, when every breaker is
        open, or on a pre-agg daemon (version skew — no breaker
        penalty). Semantics identical to ed25519_agg.verify_aggregate."""
        from tendermint_tpu.crypto import ed25519_agg

        terms = ed25519_agg.aggregate_terms(pubs, msgs, rs, s_agg)
        if terms is None:
            return False
        n = len(terms)
        if self._use_device(n) and _attempt <= self._max_retries():
            try:
                if self._kernel == "devd":
                    from tendermint_tpu.ops import devd_backend

                    try:
                        points = devd_backend.agg_batch(terms)
                    except devd_backend.AggUnsupported:
                        # healthy-but-old daemon: CPU floor, no breaker
                        # penalty, latched so the next commit skips the
                        # doomed attempt
                        points = None
                else:
                    from tendermint_tpu.ops import ed25519 as ops_ed

                    points = ops_ed.dsm_batch(terms)
                if points is not None:
                    with self._mtx:
                        self._stats["agg_batches"] += 1
                        self._stats["agg_lanes_device"] += n
                    self._note_device_success()
                    return ed25519_agg.finish_from_points(points)
            except Exception:
                logger.exception(
                    "aggregate verify via %s failed", self._kernel
                )
                self._demote_after_failure()
                return self.verify_aggregate(
                    pubs, msgs, rs, s_agg, _attempt=_attempt + 1
                )
        with self._mtx:
            self._stats["agg_lanes_cpu"] += n
        return ed25519_agg.verify_aggregate(pubs, msgs, rs, s_agg)

    def pop_primed(self, item: Item) -> bool | None:
        """Pop (single-use) the primed verdict for one item: True/False
        from a resolved batch, None if never primed, FIFO-evicted, or
        the batch failed to resolve — the caller re-verifies. The
        round-16 VoteBatcher reads its batched-vs-singleton accounting
        off this; verify_one is pop_primed + the CPU fallback."""
        with self._mtx:
            primed = self._primed.pop(item, None)
        if isinstance(primed, _PendingBatch):
            # wait OUTSIDE the mutex: this blocks on the device
            batch = primed
            primed = batch.result_for(item)
            ns = batch.take_ipc_ns()
            if ns:
                from tendermint_tpu import devd

                devd.note_batch_ipc_ns(ns)
        return primed

    def verify_one(self, pubkey: bytes, msg: bytes, sig: bytes) -> bool:
        """Single-signature path (vote-by-vote arrival). A result primed
        by prime_cache is consumed here without re-verifying; otherwise
        CPU — latency over throughput: the receive routine waits for each
        of these in turn, and a device round trip a vote made a
        16-validator height last 9-30 s on the chip (PERF.md, PR 27).
        Exists so VoteSet can take one pluggable callable."""
        primed = self.pop_primed((pubkey, msg, sig))
        if primed is not None:
            return primed
        with self._mtx:
            # by design, not a fallback: single_sigs tells the two apart
            # (cpu_sigs less single_sigs = batch lanes the host verified)
            self._stats["cpu_sigs"] += 1
            self._stats["single_sigs"] += 1
        return verify_any(pubkey, msg, sig)

    def prime_cache(self, items: list[Item]) -> None:
        """Batch-verify now (TPU when wide enough) and stash per-item
        results for imminent verify_one calls — how a burst of gossiped
        votes rides the kernel while VoteSet keeps its one-vote-at-a-time
        accept/reject semantics (SURVEY §7; ref types/vote_set.go:137-175
        verifies inline per vote). Unconsumed entries age out FIFO."""
        if not items:
            return
        oks = self.verify_batch(items)
        with self._mtx:
            for it, ok in zip(items, oks):
                self._primed[it] = bool(ok)
            while len(self._primed) > self._primed_cap:
                self._primed.pop(next(iter(self._primed)))

    def prime_cache_async(self, items: list[Item], on_done=None) -> None:
        """Pipelined prime_cache: dispatch the batch to the device NOW
        (verify_batch_async — streamed chunks on the devd backend) and
        park a pending handle per item; the first verify_one to pop one
        blocks for the batch verdicts. The caller's host work between
        dispatch and first pop (vote-set bookkeeping, the VoteBatcher's
        prepare-time screening in consensus/vote_batcher.py) overlaps
        marshal, IPC, and device compute instead of serializing behind
        them. `on_done(dt_s)` observes the dispatch→verdicts wall time
        on successful resolution. The daemon's record of the call names
        it a `vote` call (docs/device-daemon.md)."""
        if not items:
            return
        from tendermint_tpu import devd

        with devd.asking("vote"):
            resolve = self.verify_batch_async(items)
        pending = _PendingBatch(items, resolve, on_done)
        with self._mtx:
            for it in items:
                self._primed[it] = pending
            while len(self._primed) > self._primed_cap:
                self._primed.pop(next(iter(self._primed)))

    def stats(self) -> dict:
        with self._mtx:
            out = dict(self._stats)
        if self._kernel == "devd":
            # serving-path observability: fold the streamed-transport
            # counters in so a node's stats() shows the data plane.
            # FLAT numeric keys — the metrics RPC (rpc/core/handlers.py)
            # exports stats() as scalar gauges
            try:
                from tendermint_tpu.ops import devd_backend

                for k, val in devd_backend.stream_stats().items():
                    out[k if k.startswith("stream") else f"stream_{k}"] = val
            except Exception:  # noqa: BLE001 — stats must never raise
                pass
            # degradation observability (round 8): breaker state +
            # transitions + time-in-fallback, and the faults_* counters
            # (zeros unless a chaos harness is registered) — operators
            # see a sick device plane, not just a throughput dip
            try:
                out.update(devd_breaker().stats())
                from tendermint_tpu.ops import faults

                out.update(faults.global_counters())
            except Exception:  # noqa: BLE001 — stats must never raise
                pass
        return out

    # -- adapters for the call sites --------------------------------------

    def commit_batch_verifier(self, why: str = "commit"):
        """For ValidatorSet.verify_commit(batch_verifier=...): verify_batch
        with its calls named `why` on the daemon's records
        (docs/device-daemon.md)."""
        return self.asking(why, self.verify_batch)

    @staticmethod
    def asking(why: str, fn):
        """`fn` with every daemon request it sends named `why` (the
        async form names its dispatch: the request goes out in the call)."""
        from tendermint_tpu import devd

        def call(*args, **kw):
            with devd.asking(why):
                return fn(*args, **kw)

        return call

    def vote_verifier(self):
        """For VoteSet.add_vote(verifier=...)."""
        return self.verify_one


class ShardedVerifier(Verifier):
    """Verifier whose kernel inputs are sharded over a device mesh along the
    batch axis. Each chip verifies its slice; results gather to host. This
    is how a 10k-validator commit rides a v5e pod slice: 10k lanes split
    over N chips on ICI.

    Two sharded backends: "f32p" (shard_map over the pallas ladder — the
    single-chip winner, now the TPU-mesh default; per-shard body is plain
    XLA on non-TPU meshes, same math — ed25519_f32p.make_sharded_verify)
    and "f32" (pjit over the conv formulation — the non-TPU default and
    the fallback). The comb kernel does not shard; requesting it
    explicitly is an error rather than a silent misreport."""

    def __init__(self, mesh, min_tpu_batch: int | None = None):
        super().__init__(min_tpu_batch=min_tpu_batch, use_tpu=True)
        explicit = os.environ.get("TENDERMINT_TPU_KERNEL", "")
        if explicit and explicit not in ("f32", "f32p"):
            raise ValueError(
                f"ShardedVerifier shards the f32/f32p kernels; "
                f"TENDERMINT_TPU_KERNEL={explicit!r} — use the base "
                f"Verifier to run another kernel or the device daemon"
            )
        # base init may have resolved devd; this class does its own
        # in-process sharded dispatch
        self._kernel = explicit or ("f32p" if on_tpu() else "f32")
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PS

        from tendermint_tpu.ops import ed25519_f32 as ops_ed

        self.mesh = mesh
        self._n_dev = mesh.size
        # (device_id, lanes) per shard of the most recent sharded
        # dispatch — None until one runs (see shard_layout)
        self.last_shard_layout: list[tuple[int, int]] | None = None
        batch_last = NamedSharding(mesh, PS(None, "batch"))
        vec = NamedSharding(mesh, PS("batch"))
        self._verify = jax.jit(
            ops_ed._verify_impl,
            in_shardings=(batch_last, batch_last, batch_last, vec, batch_last, batch_last),
            out_shardings=vec,
        )

    def _kernel_module(self):
        # pin f32 for the inherited sync/async fallback paths — the
        # narrow-batch path must never swap onto the unsharded pallas
        # kernel (and self._kernel may be the sharded "f32p")
        import importlib

        return importlib.import_module(KERNELS["f32"])

    def verify_batch_async(self, items: list[Item], _attempt: int = 0):
        """Sharded pipelining: the pjit/shard_map dispatch is already
        asynchronous, so enqueue now and materialize in the resolver —
        same contract as the base class (which would otherwise fall back
        to the UNSHARDED kernel for async calls)."""
        n = len(items)
        if (
            n == 0
            or not self._tpu_ok
            or n < self.min_tpu_batch
            or any(len(it[0]) != 32 or len(it[2]) != 64 for it in items)
        ):
            return super().verify_batch_async(items, _attempt=_attempt)
        res = self.verify_batch(items)  # async dispatch inside; results
        # materialize before return today — acceptable: the sharded path
        # serves pod-scale batch posting, and jax's async dispatch still
        # overlaps device work with the caller's next marshal
        return lambda: res

    def verify_batch(self, items: list[Item], _attempt: int = 0) -> list[bool]:
        n = len(items)
        if n == 0:
            return []
        if any(len(it[0]) != 32 or len(it[2]) != 64 for it in items):
            # mixed key types: the base partitions and re-enters here with
            # the pure-ed25519 lanes; secp256k1 verifies on CPU
            return super().verify_batch(items, _attempt=_attempt)
        if not self._tpu_ok or n < self.min_tpu_batch:
            return super().verify_batch(items, _attempt=_attempt)
        try:
            if self._kernel == "f32p":
                from tendermint_tpu.ops import ed25519_f32p as ops_f32p

                ok_dev, valid, _n = ops_f32p.sharded_verify_arrays(
                    items, self.mesh, on_tpu()
                )
                self.last_shard_layout = shard_layout(ok_dev)
                oks = ops_f32p.materialize_verdicts(ok_dev, valid, n)
                with self._mtx:
                    self._stats["tpu_batches"] += 1
                    self._stats["tpu_sigs"] += n
                return [bool(b) for b in oks]

            import jax.numpy as jnp

            from tendermint_tpu.ops import ed25519_f32 as ops_ed

            # bucket so every device gets an equal, stable-shaped slice:
            # power-of-two rounded up to a multiple of the mesh size
            m = self._n_dev
            bucket = ops_ed._next_pow2(max(n, m))
            if bucket % m:
                bucket = ((bucket + m - 1) // m) * m
            ax, ay, ry, rs, s8, h8, valid = ops_ed.prepare_batch8(items, bucket)
            ok = self._verify(
                jnp.asarray(ax), jnp.asarray(ay), jnp.asarray(ry),
                jnp.asarray(rs), jnp.asarray(s8), jnp.asarray(h8),
            )
            self.last_shard_layout = shard_layout(ok)
            with self._mtx:
                self._stats["tpu_batches"] += 1
                self._stats["tpu_sigs"] += n
            return [bool(b) for b in (np.asarray(ok)[:n] & valid[:n])]
        except Exception:
            # round-8 latch sweep: these stay genuinely unconditional —
            # a sharded compile/dispatch failure in THIS process is
            # deterministic (same mesh, same program), so a breaker-style
            # retry would fail identically; the f32p -> f32 -> CPU ladder
            # is a one-way ratchet by design
            if self._kernel == "f32p":
                logger.exception("sharded f32p verify failed; trying f32")
                self._kernel = "f32"
                return self.verify_batch(items)
            logger.exception("sharded TPU verify failed; falling back to CPU")
            self._tpu_ok = False
            return super().verify_batch(items)


# -- merkle/hashing gateway --------------------------------------------------


def daemon_rtt_ms() -> float | None:
    """The device dispatch round trip the Hasher policy keys on, as the
    chip's OWNER reports it (`rtt_ms` in the daemon's ping), or None.
    Only the owner may dial the device, so a process that is not the
    daemon never measures this itself. The daemon reports none yet:
    whether its hash plane should serve by default is a measurement the
    benchmark PR owes (the hash kernels have never served from a chip),
    so until a daemon says otherwise every process hashes on the host
    unless TENDERMINT_TPU_HASHES=1 tells it to offload."""
    from tendermint_tpu import devd

    rep = devd.available()
    rtt = rep.get("rtt_ms") if rep is not None else None
    return float(rtt) if rtt is not None else None


# Above this measured dispatch round-trip the hash offload can't win at
# production part-batch shapes: a 1 MB part set needs >200 MB/s to beat
# the host AVX-512 path, so even zero device compute loses once the
# round trip alone exceeds ~5 ms.
HASH_RTT_MS_MAX = 5.0


class _HashFuture:
    """Join handle for a submitted-early hash job (round 14). result()
    re-raises the worker-side exception; callers on the hot path catch
    and fall back to the inline compute."""

    __slots__ = ("_evt", "_value", "_exc")

    def __init__(self):
        self._evt = threading.Event()
        self._value = None
        self._exc: BaseException | None = None

    def _finish(self, value=None, exc: BaseException | None = None) -> None:
        self._value = value
        self._exc = exc
        self._evt.set()

    def result(self, timeout: float | None = None):
        if not self._evt.wait(timeout):
            raise TimeoutError("hash submission did not complete")
        if self._exc is not None:
            raise self._exc
        return self._value


class Hasher:
    """Batched hashing gateway for the PartSet/tx-tree hot paths.

    Policy (transport-keyed): the default offloads only where the
    device's owner reports a dispatch round trip at or under
    HASH_RTT_MS_MAX (daemon_rtt_ms). A 1 MB part set needs >200 MB/s to
    beat the host AVX-512 path, so once the round trip alone passes
    ~5 ms no kernel can win. No daemon reports a round trip yet, so the
    default is host hashing everywhere; what the hash plane does on a
    chip is not measured. The one structural argument against the
    device that is independent of transport is compression-chain
    serialism (a 64 KB part = 1024 sequential SHA/RIPEMD rounds, no MXU
    help, parallel only across parts) — to be measured, not assumed.
    The streamed route (hash_stream — ops/devd_backend.hash_batch)
    frames a batch in chunks over devd and its tree frame makes
    part-set proofs free for the client. Not measured on the chip.

    Routing (resolved ONCE at construction, like Verifier's kernel):
    when offload is on and a device daemon is serving, every hash batch
    rides daemon IPC — streamed chunk frames at or above the
    ops/devd_backend width/bytes floor (mirroring
    TENDERMINT_DEVD_STREAM_MIN), single-shot below it — so this process
    never dials the chip the daemon owns (before r7, forcing
    TENDERMINT_TPU_HASHES=1 next to a serving daemon dialed in-process,
    violating the one-owner rule). With no daemon the in-process kernels
    run as before.

    The host path this competes with batches equal-length parts 16-wide
    into AVX-512 calls (native ripemd160_x16, ~1.2 GB/s; 4.9x the
    sequential loop) and builds trees with the flat level-order builder
    (merkle.simple.FlatTree, ~2.9x the recursive proofs build at the
    1 MB / 64 KB shape) — CPU here is an optimized floor, not a punt.
    Overrides: TENDERMINT_TPU_HASHES=1 forces offload (any transport),
    =0 forces CPU; TENDERMINT_TPU_DISABLE=1 forces CPU."""

    def __init__(self, min_tpu_batch: int | None = None,
                 use_tpu: bool | None = None):
        if min_tpu_batch is None:
            min_tpu_batch = int(
                _env_number("TENDERMINT_TPU_HASH_MIN_BATCH", 16, cast=int)
            )
        if use_tpu is None:
            env = os.environ.get("TENDERMINT_TPU_HASHES", "")
            if os.environ.get("TENDERMINT_TPU_DISABLE", "") == "1" or env == "0":
                use_tpu = False
            elif env == "1":
                use_tpu = True
            else:
                rtt = daemon_rtt_ms()
                use_tpu = rtt is not None and rtt <= HASH_RTT_MS_MAX
        self.min_tpu_batch = min_tpu_batch
        self._tpu_ok = use_tpu
        self._route = None
        if use_tpu:
            from tendermint_tpu import devd

            self._route = "devd" if devd.available() is not None else "local"
            if self._route == "local":
                # this process compiles the hash kernels itself
                from tendermint_tpu.jitcache import enable as _enable_cache

                _enable_cache()
        self._mtx = threading.Lock()
        self._stats = {
            "tpu_part_batches": 0, "tpu_leaves": 0,
            "tpu_tx_roots": 0, "cpu_leaves": 0,
            # batch-shape observability (same spirit as the verify
            # stream counters): bytes through the batched hash path and
            # the last/EWMA per-batch latency, so a misbehaving hash
            # transport is measurable in production, not just in benches
            "batch_bytes": 0, "batch_ms_last": 0.0, "batch_ms_avg": 0.0,
            # tx-root cache (mempool -> proposal path): reproposals and
            # gossip re-validation of an unchanged tx set never rehash
            "tx_root_cache_hits": 0,
            # round 14: submitted-early futures (pipelined proposal
            # build) — jobs queued to the submit worker, and how many
            # txs_hash() calls JOINED an in-flight early submission
            # instead of recomputing
            "submitted_jobs": 0, "tx_root_prehash_joins": 0,
            # streamed hash transport gauges, ALWAYS present (zeros off
            # the devd route) so the metrics RPC exports a stable gauge
            # set — flat numerics, same contract as Verifier's stream_*
            "stream_batches": 0, "stream_chunks_out": 0,
            "stream_lanes": 0, "stream_bytes_out": 0,
            "stream_trees": 0, "stream_reconnects": 0,
            "stream_single_batches": 0, "stream_single_lanes": 0,
        }
        # mempool->proposal tx-root cache: keyed by the tx tuple (one
        # C-level siphash pass over the raw txs — the leaf-hash tuple
        # would cost the very RIPEMD pass the cache exists to skip).
        # Cap is small on purpose: keys pin their tx bytes, and the
        # repropose/re-validate window is a handful of recent sets
        self._tx_roots: OrderedDict[tuple, bytes] = OrderedDict()
        self._tx_roots_cap = 16
        # round 14 (pipelined execution): submitted-early hash futures.
        # One daemon worker serializes submissions (the streamed devd
        # client is pooled but ordering keeps the batch-shape gauges
        # meaningful); in-flight tx roots dedupe so the consensus
        # thread's later txs_hash() JOINS the early submission instead
        # of re-hashing beside it.
        self._submit_q: "queue.Queue | None" = None
        self._submit_thread: threading.Thread | None = None
        self._inflight_tx_roots: dict[tuple, _HashFuture] = {}
        # round 11: full distribution behind batch_ms_last/_avg (one
        # observe per offload batch; scrape-only via GET /metrics)
        from tendermint_tpu.libs import telemetry

        self._batch_hist = telemetry.default_registry().histogram(
            "gateway_hash_batch_seconds",
            "hash-offload batch wall time (devd IPC or in-process kernel)",
        )

    def stats(self) -> dict:
        with self._mtx:
            out = dict(self._stats)
        if self._route == "devd":
            # live client-side hash-transport counters overlay the zeros
            # (flat numeric keys: the metrics RPC exports scalar gauges)
            try:
                from tendermint_tpu.ops import devd_backend

                for k, val in devd_backend.hash_stream_stats().items():
                    out[k if k.startswith("stream") else f"stream_{k}"] = val
            except Exception:  # noqa: BLE001 — stats must never raise
                pass
            # the SAME shared breaker the verify plane rides (round 8)
            try:
                out.update(devd_breaker().stats())
                from tendermint_tpu.ops import faults

                out.update(faults.global_counters())
            except Exception:  # noqa: BLE001 — stats must never raise
                pass
        return out

    def route(self) -> str:
        """Where this hasher's batches go, resolved at construction:
        "host", "devd" (daemon IPC) or "local" (in-process kernels)."""
        return self._route if self._tpu_ok else "host"

    def _use_offload(self, n: int) -> bool:
        """Route this batch to the offload path? On the devd route the
        breaker plane gates per batch (every breaker open = host hashing
        for THIS batch, devd routing restored by the next healthy
        probe — never the old permanent `_tpu_ok = False` latch)."""
        if not (self._tpu_ok and n >= self.min_tpu_batch):
            return False
        return self._route != "devd" or devd_plane_allow()

    def _demote_after_failure(self) -> None:
        """A hash offload raised. devd route -> the breaker plane
        (transient transport failure, recoverable). In-process kernel
        route -> permanent CPU latch, annotated per the round-8 sweep:
        a jax compile/dispatch failure in this process is deterministic
        and would recur per batch."""
        if self._route == "devd":
            devd_plane_failure()
            return
        self._tpu_ok = False

    def _note_offload_success(self) -> None:
        if self._route == "devd":
            devd_plane_success()

    def _note_batch(self, n_bytes: int, dt_s: float) -> None:
        self._batch_hist.observe(dt_s)
        ms = dt_s * 1000.0
        with self._mtx:
            s = self._stats
            s["batch_bytes"] += n_bytes
            s["batch_ms_last"] = round(ms, 3)
            s["batch_ms_avg"] = round(
                0.8 * s["batch_ms_avg"] + 0.2 * ms, 3
            ) if s["batch_ms_avg"] else round(ms, 3)

    def _offload_leaf_hashes(self, chunks: list[bytes], mode: str) -> list[bytes]:
        """One offload batch on the resolved route (devd IPC stream or
        in-process kernel). Raises on failure; callers demote to CPU."""
        if self._route == "devd":
            from tendermint_tpu.ops import devd_backend

            return devd_backend.hash_batch(chunks, mode)
        from tendermint_tpu.ops import merkle as ops_merkle

        if mode == "part":
            return ops_merkle.part_leaf_hashes(chunks)
        return ops_merkle.leaf_hashes(chunks)

    def part_leaf_hashes(self, chunks: list[bytes]) -> list[bytes]:
        """Part.Hash batch — for PartSet.from_data(hasher=...)."""
        if self._use_offload(len(chunks)):
            try:
                t0 = time.perf_counter()
                out = self._offload_leaf_hashes(chunks, "part")
                self._note_batch(
                    sum(len(c) for c in chunks), time.perf_counter() - t0
                )
                with self._mtx:
                    self._stats["tpu_part_batches"] += 1
                    self._stats["tpu_leaves"] += len(chunks)
                self._note_offload_success()
                return out
            except Exception:
                logger.exception("TPU part hashing failed; falling back to CPU")
                self._demote_after_failure()
        with self._mtx:
            self._stats["cpu_leaves"] += len(chunks)
        from tendermint_tpu import native

        # ready(), not available(): this sits on the consensus hot path,
        # and available() may synchronously run a ~minutes-long native
        # build on a fresh checkout (same rule as the verify fallback)
        if len(chunks) >= 2 and native.ready():
            # 16 equal-length parts per SIMD call (native ripemd160_x16):
            # ~5x the per-part OpenSSL loop at production shapes
            return native.ripemd160_batch(chunks)
        from tendermint_tpu.crypto.hashing import ripemd160

        return [ripemd160(c) for c in chunks]

    def part_set_tree(self, chunks: list[bytes]):
        """(leaf hashes, merkle.simple.FlatTree) for a part set when the
        offload path serves it, None when the caller should build on
        host (PartSet.from_data falls to the flat host builder). On the
        devd route ONE streamed pass returns leaf digests AND every
        internal tree node (the hash_stream tree frame), so proofs cost
        this process zero hashing; the in-process route reads the same
        node buffer off the tree kernel (ops/merkle)."""
        if not self._use_offload(len(chunks)):
            return None
        from tendermint_tpu.merkle.simple import FlatTree

        try:
            t0 = time.perf_counter()
            if self._route == "devd":
                from tendermint_tpu.ops import devd_backend

                digests, nodes = devd_backend.hash_tree(chunks, "part")
                digests = [bytes(d) for d in digests]
                tree = FlatTree.from_nodes(
                    len(chunks), digests + [bytes(x) for x in nodes]
                )
            else:
                from tendermint_tpu.ops import merkle as ops_merkle

                digests = ops_merkle.part_leaf_hashes(chunks)
                tree = FlatTree.from_nodes(
                    len(chunks),
                    ops_merkle.tree_nodes_from_leaf_digests(digests),
                )
            self._note_batch(
                sum(len(c) for c in chunks), time.perf_counter() - t0
            )
            with self._mtx:
                self._stats["tpu_part_batches"] += 1
                self._stats["tpu_leaves"] += len(chunks)
            self._note_offload_success()
            return digests, tree
        except Exception:
            logger.exception("TPU part-set tree failed; falling back to CPU")
            self._demote_after_failure()
            return None

    # -- submitted-early futures (round 14, pipelined proposal build) -----

    def _submit(self, fn) -> _HashFuture:
        """Queue `fn` on the single daemon submit worker; returns the
        join handle. The worker is lazy: processes that never submit
        (most tests, the verify-only planes) pay nothing."""
        fut = _HashFuture()
        with self._mtx:
            if self._submit_q is None:
                self._submit_q = queue.Queue()
                self._submit_thread = threading.Thread(
                    target=self._submit_loop, daemon=True,
                    name="gw.hashSubmit",
                )
                self._submit_thread.start()
            self._stats["submitted_jobs"] += 1
            q = self._submit_q
        q.put((fut, fn))
        return fut

    def _submit_loop(self) -> None:
        while True:
            fut, fn = self._submit_q.get()
            try:
                fut._finish(value=fn())
            except BaseException as exc:  # noqa: BLE001 — joined by caller
                fut._finish(exc=exc)

    def submit_tx_root(self, txs: list[bytes]) -> _HashFuture:
        """Start hashing the tx root NOW (streamed devd plane / AVX /
        CPU ladder) and return a future; a later tx_merkle_root() on the
        same tx set joins the in-flight job instead of recomputing.
        consensus/state.create_proposal_block submits right after the
        mempool reap so the root hashes while the commit/evidence/header
        assemble."""
        key = tuple(txs)
        done = _HashFuture()
        with self._mtx:
            cached = self._tx_roots.get(key)
            if cached is not None:
                self._tx_roots.move_to_end(key)
                done._finish(value=cached)
                return done
            fut = self._inflight_tx_roots.get(key)
            if fut is not None:
                return fut
            fut = _HashFuture()
            self._inflight_tx_roots[key] = fut

        def work():
            try:
                root = self._tx_merkle_root_uncached(txs)
            except BaseException as exc:  # noqa: BLE001 — joined by caller
                with self._mtx:
                    self._inflight_tx_roots.pop(key, None)
                fut._finish(exc=exc)
                return
            with self._mtx:
                # resolve BEFORE clearing in-flight: a joiner either sees
                # the in-flight future (and gets this root) or the LRU
                self._tx_roots[key] = root
                while len(self._tx_roots) > self._tx_roots_cap:
                    self._tx_roots.popitem(last=False)
            fut._finish(value=root)
            with self._mtx:
                self._inflight_tx_roots.pop(key, None)

        self._submit(work)
        return fut

    def submit_part_set_tree(self, chunks: list[bytes]) -> _HashFuture:
        """part_set_tree as a future: the devd/AVX round trip overlaps
        the caller's Part-object construction (types/part_set.py joins
        before building proofs). Resolves to (digests, FlatTree) or None
        exactly like part_set_tree."""
        return self._submit(lambda: self.part_set_tree(chunks))

    def tx_merkle_root(self, txs: list[bytes]) -> bytes:
        """Txs.Hash — the tx-tree root (types/tx.go:33-46), batched when
        wide enough. Injected into types/tx via set_batch_tx_root at node
        assembly so every block build/validate rides it. Roots are
        memoized per tx set (small LRU): the mempool -> proposal path
        recomputes the same root on repropose, block re-validation, and
        gossip receipt — those now cost one dict lookup, no rehash. A
        root submitted early (submit_tx_root) is JOINED, not recomputed."""
        key = tuple(txs)
        with self._mtx:
            cached = self._tx_roots.get(key)
            if cached is not None:
                self._tx_roots.move_to_end(key)
                self._stats["tx_root_cache_hits"] += 1
                return cached
            fut = self._inflight_tx_roots.get(key)
        if fut is not None:
            try:
                root = fut.result(timeout=120)
                with self._mtx:
                    self._stats["tx_root_prehash_joins"] += 1
                return root
            except Exception:
                logger.exception(
                    "early tx-root submission failed; recomputing inline"
                )
        root = self._tx_merkle_root_uncached(txs)
        with self._mtx:
            self._tx_roots[key] = root
            while len(self._tx_roots) > self._tx_roots_cap:
                self._tx_roots.popitem(last=False)
        return root

    def _tx_merkle_root_uncached(self, txs: list[bytes]) -> bytes:
        if self._use_offload(len(txs)):
            try:
                t0 = time.perf_counter()
                if self._route == "devd":
                    from tendermint_tpu.ops import devd_backend

                    # tree=True: the daemon's tree kernel returns every
                    # internal node; the root is the last one — zero
                    # host hashing on the whole path
                    digests, nodes = devd_backend.hash_tree(txs, "leaf")
                    out = bytes(nodes[-1]) if nodes else bytes(digests[0])
                else:
                    from tendermint_tpu.ops import merkle as ops_merkle

                    out = ops_merkle.merkle_root_from_leaf_digests(
                        ops_merkle.leaf_hashes(txs)
                    )
                self._note_batch(
                    sum(len(t) for t in txs), time.perf_counter() - t0
                )
                with self._mtx:
                    self._stats["tpu_tx_roots"] += 1
                    self._stats["tpu_leaves"] += len(txs)
                self._note_offload_success()
                return out
            except Exception:
                logger.exception("TPU tx hashing failed; falling back to CPU")
                self._demote_after_failure()
        from tendermint_tpu.merkle.simple import simple_hash_from_byteslices

        with self._mtx:
            self._stats["cpu_leaves"] += len(txs)
        return simple_hash_from_byteslices(txs)


# -- module-level default instances ------------------------------------------

_default_verifier: Verifier | None = None
_default_hasher: Hasher | None = None
_default_mtx = threading.Lock()


def default_verifier() -> Verifier:
    global _default_verifier
    with _default_mtx:
        if _default_verifier is None:
            _default_verifier = Verifier()
        return _default_verifier


def default_hasher() -> Hasher:
    global _default_hasher
    with _default_mtx:
        if _default_hasher is None:
            _default_hasher = Hasher()
        return _default_hasher
