"""Run every BASELINE.md config bench and record results in BENCHES.json
(BENCHES.host.json under TENDERMINT_TPU_DISABLE=1).

Configs (BASELINE.md):
  1 testnet   — 4-validator kvstore net, commit-hash parity
  2 headline  — VerifyCommit microbench (repo-root bench.py, driver-run)
  3 partset   — 1MB/64KB PartSet Merkle + proofs, plus the r7 hash-plane
                rows: sim-transport streamed-vs-single-shot hash offload
                (asserted >= 1.3x) and flat-vs-recursive host proofs
                builder (asserted >= 1.5x); writes BENCH_r07.json with
                per-row platform, chip-free
  4 fastsync  — pipelined catch-up replay, 1000 validators
  5 mempool   — 50k-tx CheckTx burst + signed-tx gated burst
  6 devd_stream — serving-path transport: single-shot vs streamed devd
                  (writes BENCH_r06.json; asserts the streamed win)
  7 chaos      — device-plane failure shape: recovery time after daemon
                 kill/restart + degraded-mode (breaker-open CPU
                 fallback) throughput delta (writes BENCH_r08.json;
                 chip-free, asserts the recovery floor)
  8 wal        — host durability plane: group-commit vs fsync-per-record
                 WAL throughput, repair/recovery scan on a torn 10k-record
                 log, byte-offset torture smoke (writes BENCH_r09.json;
                 chip-free BY CONSTRUCTION, asserts the >=1.3x floor)
  9 statesync   — cold-start plane: snapshot restore vs fast-sync replay
                  on a 300-block signedkv chain + streamed-vs-single-shot
                  chunk verification on the sim transport (writes
                  BENCH_r10.json; chip-free rows asserted >=1.3x, the
                  live-daemon row appends when a daemon serves a chip)
 10 telemetry    — observability plane: hot-path instrumentation overhead
                  on the mempool signed-burst gate (asserted <2%) +
                  Prometheus exposition smoke (writes the "telemetry"
                  section of BENCH_r11.json; chip-free)
 11 rpc_load     — ws broadcast burst against a live node + the round-11
                  scrape-cost row: GET /metrics hammered under load must
                  not move consensus height_seconds (writes the
                  "rpc_scrape" section of BENCH_r11.json; chip-free)
 12 netchaos     — network plane: real-TCP testnet (in-repo
                  SecretConnection + ops/netfaults link proxies) through
                  partition-heal cycles + listener churn; recovery time
                  and committed-tx/s recorded, halt-under-partition and
                  byte-identical convergence asserted (writes
                  BENCH_r12.json; chip-free)
 14 pipeline     — execution plane: committed-tx/s at saturating signed
                  mempool load on a durable single-validator chain, seed
                  plane (inline finalize + per-tx DeliverTx dispatch +
                  per-tx pure-python sig verify) vs the round-14 plane
                  (staged pipelined finalize + grouped dispatch + one
                  gateway sig batch per block + sharded kv fold); byte-
                  identity of all chains asserted (writes BENCH_r14.json;
                  chip-free)
 15 fleet        — fleet observability plane: 4-node real-TCP net scraped
                  by ops/fleet (GET /metrics + consensus_trace +
                  GET /health only) — cross-node timeline reconstructed
                  (propagation lag / quorum time / commit skew), the
                  partition arm detected+healed off /health, per-peer
                  instrumentation overhead bounded <2% (writes
                  BENCH_r15.json; chip-free)
 16 committee    — big-committee vote plane: live 100-400-validator
                  consensus (in-process committee pump) batched vs
                  per-vote vote-signature verification — byte-identical
                  chains asserted, batched >= 1.3x at 100 validators —
                  plus commit-verify latency and aggregate-commit size
                  rows vs validator count (writes BENCH_r16.json;
                  chip-free, devd rows auto-join when a daemon serves)
 17 txtrace      — request-level observability: sampled per-tx lifecycle
                  spans on a live committing chain (per-stage p50/p99,
                  spans-through-commit asserted within 10% of measured
                  end-to-end latency), tracing + flight-recorder
                  overhead bound asserted <2% on the signed-burst
                  shape, wedge-dump artifact row (writes BENCH_r17.json;
                  chip-free)
 18 wan          — internet-scale adversarial tier: real-TCP testnet
                  under named WAN profiles (seeded latency/jitter/loss/
                  bandwidth via ops/netfaults) — heights/s + commit
                  skew per profile off the ops/fleet timelines — plus
                  the flood-shed row: heights cadence asserted >= 1/3
                  baseline while a hostile peer floods garbage
                  signatures at the sig gate, shed asserted visible in
                  p2p_adversary_flood_txs_rejected (writes
                  BENCH_r18.json; chip-free)
 19 retention    — bounded-retention lifecycle: steady-state disk
                  bytes/height on a pruned vs archive node (asserted
                  bounded by retention, not chain length) + adversarial
                  statesync offerer ban latency (forged / corrupt /
                  stalling each banned while the restore completes from
                  the honest source; writes BENCH_r19.json; chip-free)
 20 localnet     — hundreds-of-nodes process tier: 10/25/50 real node
                  processes (ops/localnet through netfaults proxies,
                  50 under the continental WAN profile on a ring);
                  heights/s, duplicate-vote ratio, gossip bytes/height
                  vs node count; has-vote dedup A/B at n=10 asserted
                  to reduce the ratio; process-scale partition-heal
                  (writes BENCH_r20.json; chip-free)
 21 devd_shard   — sharded device plane: aggregate verify sigs/s + hash
                  MB/s through ops/devd_shard vs 1/2/4 sim daemon
                  fleets (>= 1.6x at 2 daemons asserted, digests
                  byte-identical across fleet sizes) + the
                  kill-one-mid-burst failover row: exact per-lane
                  verdicts through re-dispatch, breaker open/recovery
                  latencies (writes BENCH_r21.json; chip-free)
 13 statetree    — authenticated app-state commitment: incremental
                  commit vs full tree rebuild, proof correctness rows,
                  delta-vs-full snapshot bytes (delta asserted <= 0.5x
                  full at the larger state size), streamed vs
                  single-shot node hashing on the sim transport (writes
                  BENCH_r13.json; chip-free rows asserted, the
                  live-daemon row appends when a daemon serves a chip)

24 replica      — verified read-replica tier: the replica_flood
                  localnet scenario (cadence flat under flood, byte
                  identity, 100% tamper rejection) + the serving
                  ladder — verified reads/s and relayed WS events/s
                  direct-to-validator vs 1/2/4 replica processes
                  (writes BENCH_r24.json; chip-free)

Each bench is its own process (the TPU is exclusive per process).
Usage: python benches/run_all.py [--skip testnet,...]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCHES = {
    "1_testnet": [sys.executable, "benches/bench_testnet.py"],
    "2_verify_commit": [sys.executable, "bench.py"],
    "3_partset": [sys.executable, "benches/bench_partset.py"],
    "4_fastsync": [sys.executable, "benches/bench_fastsync.py"],
    "5_mempool": [sys.executable, "benches/bench_mempool.py"],
    "6_devd_stream": [sys.executable, "benches/bench_devd_stream.py"],
    "7_chaos": [sys.executable, "benches/bench_chaos.py"],
    "8_wal": [sys.executable, "benches/bench_wal.py"],
    "9_statesync": [sys.executable, "benches/bench_statesync.py"],
    "10_telemetry": [sys.executable, "benches/bench_telemetry.py"],
    "11_rpc_load": [sys.executable, "benches/bench_rpc_load.py"],
    "12_netchaos": [sys.executable, "benches/bench_netchaos.py"],
    "13_statetree": [sys.executable, "benches/bench_statetree.py"],
    "14_pipeline": [sys.executable, "benches/bench_pipeline.py"],
    "15_fleet": [sys.executable, "benches/bench_fleet.py"],
    "16_committee": [sys.executable, "benches/bench_committee.py"],
    "17_txtrace": [sys.executable, "benches/bench_txtrace.py"],
    "18_wan": [sys.executable, "benches/bench_wan.py"],
    "19_retention": [sys.executable, "benches/bench_retention.py"],
    "20_localnet": [sys.executable, "benches/bench_localnet.py"],
    "21_devd_shard": [sys.executable, "benches/bench_devd_shard.py"],
    "22_upgrade": [sys.executable, "benches/bench_upgrade.py"],
    "23_overload": [sys.executable, "benches/bench_overload.py"],
    "24_replica": [sys.executable, "benches/bench_replica.py"],
}


def main() -> int:
    skip = set()
    for a in sys.argv[1:]:
        if a.startswith("--skip"):
            skip = set(a.split("=", 1)[1].split(","))
    results: dict = {"recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    # One process per chip, and this parent is not it: it never touches
    # JAX. Either a device daemon serves an accelerator (every sub-bench
    # then rides it over IPC — the gateway auto-selects the devd
    # backend), or the operator said TENDERMINT_TPU_DISABLE=1 and the
    # whole run measures the host under the host's name. Anything else
    # is an error: no chip found and not told to do without one.
    env = dict(os.environ)
    if env.get("TENDERMINT_TPU_DISABLE", "") == "1":
        results["device"] = "host (TENDERMINT_TPU_DISABLE=1)"
    else:
        sys.path.insert(0, ROOT)
        from tendermint_tpu import devd

        rep = devd.available(timeout=3.0)
        if rep is None or rep.get("platform") == "cpu":
            print(
                "run_all: no device daemon is serving an accelerator; start "
                "`python -m tendermint_tpu.devd`, or set "
                "TENDERMINT_TPU_DISABLE=1 to measure the host path under "
                "its own name",
                file=sys.stderr,
            )
            return 3
        results["device"] = (
            f"devd daemon ({rep.get('platform')} {rep.get('device_kind')}, "
            f"pid {rep.get('pid')})"
        )
        print(f"run_all: {results['device']}; benches ride the daemon",
              file=sys.stderr)
    failed = False
    for name, cmd in BENCHES.items():
        if any(s in name for s in skip):
            continue
        print(f"== {name}: {' '.join(cmd[1:])}", file=sys.stderr)
        t0 = time.time()
        try:
            proc = subprocess.run(
                cmd, cwd=ROOT, capture_output=True, text=True, timeout=1800, env=env
            )
        except subprocess.TimeoutExpired as exc:
            results[name] = {"error": f"timeout after {exc.timeout}s"}
            failed = True
            print(f"   TIMEOUT ({time.time()-t0:.0f}s)", file=sys.stderr)
            continue
        line = next(
            (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")), None
        )
        if proc.returncode != 0 or line is None:
            results[name] = {"error": (proc.stderr or proc.stdout)[-2000:]}
            failed = True
            print(f"   FAILED ({time.time()-t0:.0f}s)", file=sys.stderr)
            continue
        results[name] = json.loads(line)
        print(f"   {line} ({time.time()-t0:.0f}s)", file=sys.stderr)
    # a host run is a different result, not a stale device one: it gets
    # its own file, so neither record ever overwrites the other
    out = os.path.join(
        ROOT,
        "BENCHES.host.json" if results["device"].startswith("host")
        else "BENCHES.json",
    )
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"wrote {out}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
