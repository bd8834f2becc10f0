"""Headline benchmark: VerifyCommit throughput (BASELINE.md north star).

Measures batched Ed25519 commit verification — the reference's hottest
path (types/validator_set.go:220-264: N sequential verifies per block) —
through the PRODUCTION gateway path (ops/gateway.py Verifier, which
selects the platform-default verify kernel — the pallas fp32 ladder
ops/ed25519_f32p.py on TPU; see gateway.KERNELS), against our own CPU
reference loop (the Go-equivalent baseline; upstream publishes no
numbers, BASELINE.md).

The accelerator measurement is SUSTAINED pipelined throughput, shaped
like a fast-syncing node streaming commits through the verifier:
- prep threads marshal batches and enqueue the device kernel
  (gateway.verify_batch_async — host marshal overlaps device execution);
- resolver threads block on results CONCURRENTLY: each result fetch pays
  a dispatch (and, through the daemon, an IPC) round trip, and
  overlapping fetches keeps that off the device's critical path.
Results are order-preserved and parity-checked against the CPU verifier
on a mixed valid/tampered sample.

CPU baseline methodology (pinned; round-2 review flagged run-to-run
wobble): fixed 512-signature sample, best-of-3 passes (max rate =
min time), same process, measured before any device work starts.

Prints ONE JSON line:
  {"metric": "verify_commit_sigs_per_sec", "value": N, "unit": "sigs/s",
   "vs_baseline": N / cpu_sigs_per_sec}
"""

from __future__ import annotations

import json
import os
import sys
import time

from tendermint_tpu.jitcache import enable as _enable_jit_cache

_enable_jit_cache()

BATCH = int(os.environ.get("BENCH_BATCH", "4096"))
N_BATCHES = int(os.environ.get("BENCH_N_BATCHES", "32"))
CPU_SAMPLE = int(os.environ.get("BENCH_CPU_SAMPLE", "512"))
CPU_PASSES = int(os.environ.get("BENCH_CPU_PASSES", "3"))
PREP_THREADS = int(os.environ.get("BENCH_PREP_THREADS", "2"))
RESOLVE_THREADS = int(os.environ.get("BENCH_RESOLVE_THREADS", "4"))


def _make_items(n: int, salt: int = 0):
    from tendermint_tpu.crypto import ed25519 as ed

    # 64 distinct validators signing vote-like canonical messages, cycled
    # to n — matches a real commit (few keys, many (H,R) messages).
    seeds = [bytes([i]) * 32 for i in range(64)]
    pubs = [ed.public_key(s) for s in seeds]
    items = []
    for i in range(n):
        k = i % 64
        msg = (
            b'{"chain_id":"bench","vote":{"block_id":{},"height":%d,'
            b'"round":%d,"type":2,"validator_index":%d}}'
            % (1 + i // 64, salt, k)
        )
        items.append((pubs[k], msg, ed.sign(seeds[k], msg)))
    return items


def main() -> None:
    import queue as _q
    import threading as _t

    from tendermint_tpu.crypto import ed25519 as ed_cpu
    from tendermint_tpu.ops.gateway import Verifier

    if os.environ.get("TENDERMINT_TPU_DISABLE", "") == "1":
        # told to measure the host: the number is named for what it is
        platform = "cpu (TENDERMINT_TPU_DISABLE)"
    else:
        # One process per chip (libtpu gives it to one process):
        # 1. a serving device daemon holds the chip with warmed kernels —
        #    this process is its client and never loads libtpu
        #    (resolve_platform waits, bounded, for a daemon that is still
        #    claiming or warming);
        # 2. told a direct kernel (TENDERMINT_TPU_KERNEL), this process
        #    is the owner for its whole run: it initialises JAX itself
        #    and the device must be an accelerator;
        # 3. anything else is an error — a run that finds no chip and was
        #    not told TENDERMINT_TPU_DISABLE=1 exits non-zero instead of
        #    measuring the host under a device's name.
        from tendermint_tpu import devd
        from tendermint_tpu.ops import gateway

        explicit_kernel = os.environ.get("TENDERMINT_TPU_KERNEL", "")
        told = gateway.resolve_platform()
        daemon = devd.available(timeout=3.0)
        if explicit_kernel == "devd" and daemon is None:
            print("bench: TENDERMINT_TPU_KERNEL=devd but no daemon is "
                  "serving a device", file=sys.stderr)
            raise SystemExit(3)
        if explicit_kernel == "devd" or (
            not explicit_kernel and daemon is not None
            and daemon.get("platform") != "cpu"
        ):
            # route through the daemon only when it holds REAL hardware
            # (or the operator explicitly asked): an ACCEPT_CPU daemon
            # must not produce an unmarked CPU-over-IPC headline number
            os.environ["TENDERMINT_TPU_KERNEL"] = "devd"
            platform = f"{daemon.get('platform')} (via devd)"
            print(
                f"bench: device daemon serving (platform="
                f"{daemon.get('platform')}, kind={daemon.get('device_kind')}, "
                f"warmed={daemon.get('warmed')})",
                file=sys.stderr,
            )
        elif explicit_kernel and daemon is None:
            import jax

            dev = jax.devices()[0]  # answers or raises: this process owns it
            if dev.platform == "cpu":
                print("bench: no accelerator (JAX initialised the cpu "
                      "backend); set TENDERMINT_TPU_DISABLE=1 to measure the "
                      "host path under its own name", file=sys.stderr)
                raise SystemExit(3)
            gateway.set_platform(dev.platform)
            platform = f"{dev.platform} {dev.device_kind} (in-process)"
        else:
            print(f"bench: no chip to measure (platform resolved to {told!r}, "
                  "no device daemon serving an accelerator, no direct kernel "
                  "named); start `python -m tendermint_tpu.devd` or set "
                  "TENDERMINT_TPU_DISABLE=1", file=sys.stderr)
            raise SystemExit(3)

    # the CPU fallback path rides the native batch verifier; build it NOW
    # (fresh clone: ~1 min) so a missing .so can't silently demote the
    # fallback measurement to the per-item python loop
    from tendermint_tpu import native as _native

    _native.available()

    chunks = [_make_items(BATCH, salt) for salt in range(N_BATCHES)]
    verifier = Verifier(min_tpu_batch=1)

    # --- CPU baseline: the reference-faithful sequential loop ------------
    # (best-of-k over a fixed sample pins the methodology across rounds)
    cpu_rate = 0.0
    for _ in range(CPU_PASSES):
        t0 = time.perf_counter()
        for pub, msg, sig in chunks[0][:CPU_SAMPLE]:
            assert ed_cpu.verify(pub, msg, sig)
        cpu_rate = max(cpu_rate, CPU_SAMPLE / (time.perf_counter() - t0))

    # warmup (compile) through the production path
    ok = verifier.verify_batch(chunks[0])
    assert all(ok), "warmup verify failed"

    # --- sustained pipelined throughput (best-of-k: a one-chip machine
    # shares its host's cores, so single passes can catch noise) ----------
    PASSES = int(os.environ.get("BENCH_PASSES", "2"))
    elapsed = float("inf")
    for _ in range(PASSES):
        results: list = [None] * N_BATCHES
        next_idx = {"v": 0}
        idx_mtx = _t.Lock()
        dispatched: _q.Queue = _q.Queue(maxsize=PREP_THREADS + RESOLVE_THREADS)

        def prep_worker():
            while True:
                with idx_mtx:
                    i = next_idx["v"]
                    if i >= N_BATCHES:
                        return
                    next_idx["v"] = i + 1
                dispatched.put((i, verifier.verify_batch_async(chunks[i])))

        def resolve_worker():
            while True:
                item = dispatched.get()
                if item is None:
                    return
                i, resolve = item
                results[i] = resolve()

        t0 = time.perf_counter()
        preps = [_t.Thread(target=prep_worker, daemon=True) for _ in range(PREP_THREADS)]
        resolvers = [
            _t.Thread(target=resolve_worker, daemon=True) for _ in range(RESOLVE_THREADS)
        ]
        for th in preps + resolvers:
            th.start()
        for th in preps:
            th.join()
        for _ in resolvers:
            dispatched.put(None)
        for th in resolvers:
            th.join()
        elapsed = min(elapsed, time.perf_counter() - t0)
        assert all(r is not None and all(r) for r in results), "sustained verify failed"
    total = BATCH * N_BATCHES
    rate = total / elapsed

    # --- parity check: TPU verdicts == CPU verdicts on a mixed sample ----
    sample = chunks[0][:64]
    tampered = [
        (p, m, sig[:10] + bytes([sig[10] ^ 1]) + sig[11:])
        for p, m, sig in chunks[1][:64]
    ]
    mixed = sample + tampered
    tpu_verdicts = verifier.verify_batch(mixed)
    cpu_verdicts = [ed_cpu.verify(p, m, s) for p, m, s in mixed]
    assert tpu_verdicts == cpu_verdicts, "TPU/CPU parity failure"

    stats = verifier.stats()
    print(
        json.dumps(
            {
                "metric": "verify_commit_sigs_per_sec",
                "value": round(rate, 1),
                "unit": "sigs/s",
                "vs_baseline": round(rate / cpu_rate, 2),
                "detail": {
                    "batch": BATCH,
                    "n_batches": N_BATCHES,
                    "elapsed_s": round(elapsed, 3),
                    "cpu_sigs_per_sec": round(cpu_rate, 1),
                    "cpu_methodology": f"best-of-{CPU_PASSES} over {CPU_SAMPLE} fixed sigs",
                    "platform": platform,
                    "gateway_stats": stats,
                    "parity": "ok",
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
