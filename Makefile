# Targets mirror the reference Makefile's test tiers
# (/root/reference/Makefile:27-39): `test` = unit suite, `test_race` =
# the race-discipline tier (lock-order-graph instrumentation — the
# Python analogue of `go test -race`, see libs/racecheck.py),
# `test_integrations` = the multi-node network scenarios.
#
# The reference's integration tier runs in docker containers
# (test/p2p/test.sh, test/docker/). Containers are OUT OF ENVIRONMENTAL
# SCOPE here — no docker daemon exists in this environment — so
# test_integrations runs the process tier: the same six scenarios
# (basic, atomic_broadcast, fast_sync, kill_all, seeds, pex) as real
# node processes over real TCP with real SIGKILL crash semantics
# (test/p2p/scenarios.py; see test/p2p/README.md). The authored docker
# tier (test/p2p/run_docker.sh) remains for docker-capable hosts.

PY ?= python
# tier1 uses bash process features (PIPESTATUS); everything else is sh-safe
SHELL := /bin/bash

test:
	$(PY) -m pytest tests/ -q

# The ROADMAP.md tier-1 verify command, verbatim — the bar every PR must
# hold (dots no worse than the seed) — plus the chip-free hash-stream
# smoke (the two asserted BENCH_r07 rows: streamed hash offload >= 1.3x
# single-shot on the sim transport, flat host builder >= 1.5x recursive).
tier1: hash-stream-smoke chaos-smoke wal-torture-smoke statesync-smoke statetree-smoke metrics-smoke net-chaos-smoke wan-smoke pipeline-smoke fleet-smoke committee-smoke txtrace-smoke retention-smoke localnet-smoke shard-smoke upgrade-smoke overload-smoke replica-smoke
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# Chip-free bench smoke: every BASELINE config on the pinned CPU backend,
# so a transport/serving-path regression fails fast without hardware
# (bench_devd_stream asserts the streamed-vs-single-shot win;
# bench_partset asserts the hash-stream + flat-builder wins).
bench-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_DISABLE=1 $(PY) benches/run_all.py

# Hash-plane smoke, chip-free and fast (~30 s): only bench_partset's two
# asserted rows — sim-transport hash_stream and the flat host builder —
# with no jax offload compile. Runs as part of `make tier1`.
hash-stream-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_PARTSET_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_partset.py

# Chaos smoke, chip-free and fast (~30 s): a reduced FaultPlan pass of
# bench_chaos.py — breaker-open degraded throughput + recovery-time
# floor after daemon kill/restart. Runs as part of `make tier1` (the
# full fault matrix lives in tests/test_chaos_devd.py, incl. the
# slow-marked 20-block soak).
chaos-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_CHAOS_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_chaos.py

# WAL torture smoke, chip-free BY CONSTRUCTION (~10 s): bench_wal.py's
# reduced pass — group-commit >= 1.3x fsync-per-record floor, repair scan
# on a torn 10k-record log, and a byte-offset truncation sweep over the
# tail records, every offset recovering (the full crash-model tiers live
# in tests/test_wal_repair.py + tests/test_wal_torture.py, incl. the
# slow-marked subprocess sweep). Runs as part of `make tier1`.
wal-torture-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_WAL_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_wal.py

# State-sync smoke, chip-free (~30 s): bench_statesync.py's reduced pass —
# one producer -> light-verified restore round trip on a signedkv chain
# with an injected corrupt chunk REJECTED, restore-vs-replay, and the
# sim-transport streamed chunk-verify floor (>=1.3x). Runs as part of
# `make tier1` (the protocol/reactor matrix lives in
# tests/test_statesync.py, incl. the slow-marked 1k-block restore soak).
statesync-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_STATESYNC_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_statesync.py

# State-tree smoke, chip-free (~20 s): bench_statetree.py's reduced pass —
# authenticated-tree build + incremental-commit-vs-rebuild floor, proof
# correctness rows (membership/absence verify, tamper/wrong-root refused),
# and a full->delta snapshot round trip with an injected corrupt chunk
# REJECTED (the full matrix lives in tests/test_statetree.py +
# tests/test_statesync_delta.py). Runs as part of `make tier1`.
statetree-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_STATETREE_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_statetree.py

# Network chaos smoke, chip-free (~40 s): bench_netchaos.py's reduced
# pass — a 4-node REAL-TCP testnet (in-repo SecretConnection on every
# link, ops/netfaults proxies in the middle) commits through one
# partition-heal cycle + one listener churn, recovery time asserted and
# final state byte-identical (the full scenario matrix lives in
# tests/test_netchaos.py, incl. the slow-marked 5-node soak). Runs as
# part of `make tier1`.
net-chaos-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_NETCHAOS_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_netchaos.py

# WAN/adversary smoke, chip-free (~60 s): bench_wan.py's reduced pass —
# a 4-node real-TCP signedkv net under ONE seeded WAN profile
# (continental latency/jitter/loss via ops/netfaults WanProfile) with
# heights/s + commit skew recorded off the ops/fleet timelines, then one
# mempool flood burst: a hostile peer pushes garbage signatures at the
# sig gate, the shed asserted visible in telemetry and the commit
# cadence asserted >= 1/3 of baseline, final state byte-identical (the
# full profile matrix + adversary catalog lives in tests/test_netchaos.py,
# incl. the slow-marked WAN soak). Runs as part of `make tier1`.
wan-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_WAN_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_wan.py

# Pipeline smoke, chip-free (~10 s): bench_pipeline.py's reduced pass —
# a real single-validator durable chain committing the same deterministic
# signed workload on the seed execution plane vs the round-14 pipelined
# plane: per-height byte-identity (block hash / part-set root / app hash
# / txs) asserted across runs, the committed-tx/s floor asserted, and
# the sharded kvstore fold's VersionedTree root asserted byte-identical
# to serial apply. Runs as part of `make tier1` (the full matrix lives
# in tests/test_pipeline.py + the pipeline crash tiers in
# tests/test_wal_torture.py).
pipeline-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_PIPELINE_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_pipeline.py

# Fleet observability smoke, chip-free (~40 s): bench_fleet.py's reduced
# pass — a 4-node real-TCP net scraped by ops/fleet (GET /metrics +
# consensus_trace + GET /health only): per-height cross-node timeline
# reconstructed (propagation lag / quorum-formation time / commit skew),
# the partition arm detected and healed purely off /health, and the
# round-15 per-peer instrumentation overhead bounded <2% à la BENCH_r11
# (the full scenario matrix lives in tests/test_netchaos.py). Runs as
# part of `make tier1`.
fleet-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_FLEET_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_fleet.py

# Big-committee smoke, chip-free (~10 s): bench_committee.py's reduced
# pass — a LIVE 100-validator consensus run (in-process committee pump)
# batched vs per-vote vote verification with per-height byte-identity
# (block hash / part-set root / app hash) asserted and batched >= 1.3x
# per-vote blocks/s asserted, plus the commit-verify and
# aggregate-commit object rows at 4/100 validators (the full 4-400
# matrix writes BENCH_r16.json). Runs as part of `make tier1`.
committee-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_COMMITTEE_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_committee.py

# Telemetry smoke, chip-free (~20 s): bench_telemetry.py's reduced pass —
# boot a node, scrape GET /metrics (valid 0.0.4 text, >= 40 families
# spanning every plane), pull one consensus_trace (segments sum to the
# height wall clock within 5%), and the hot-path instrumentation
# overhead guard on the mempool signed-burst gate (asserted <2%).
# Runs as part of `make tier1`.
metrics-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_TELEMETRY_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_telemetry.py

# Tx-lifecycle tracing smoke, chip-free (~45 s): bench_txtrace.py's
# reduced pass — the per-tx span recorder on a live committing node
# (every completed trace's spans-through-commit asserted to sum within
# 10% of its measured end-to-end commit latency), the tracing +
# flight-recorder overhead bound on the mempool signed-burst shape
# asserted <2%, and a flight-record wedge dump written + parsed back.
# Runs as part of `make tier1` (the contract matrix lives in
# tests/test_txtrace.py + tests/test_flightrec.py; the netchaos
# partition wedge-diagnosis scenario in tests/test_netchaos.py).
txtrace-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_TXTRACE_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_txtrace.py

# Retention smoke, chip-free (~60 s): bench_retention.py's reduced pass
# — the ~200-height bounded-retention run: a live sqlite-backed node
# with [pruning] + the statesync producer armed vs an archive twin,
# steady-state disk bytes/height asserted bounded by retention (ratio
# floor), then the adversarial statesync offerer burst: forged-manifest,
# corrupt-chunk, and stalling offerers each BANNED (scrape-visible,
# latency recorded) while a joining node's restore completes from the
# honest source. Runs as part of `make tier1` (the slow retention soak +
# offerer matrix under WAN live in tests/test_netchaos.py; the crash
# tier in tests/test_retention.py).
retention-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_RETENTION_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_retention.py

# — the hundreds-of-nodes localnet tier, smoke-sized: a 5-node fleet of
# REAL node processes (ops/localnet) peered through netfaults link
# proxies converges byte-identically and reports its duplicate-vote
# ratio off live scrapes (~60 s; the 10/25/50-node scale ladder +
# dedup A/B + process-scale partition-heal run on the full bench).
localnet-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_LOCALNET_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_localnet.py

# Sharded-device-plane smoke, chip-free (~30 s): bench_devd_shard.py's
# reduced pass — 1-vs-2 sim daemon fleets behind ops/devd_shard with the
# aggregate sigs/s scaling floor asserted (>= 1.6x at 2 daemons), digest
# parity across fleet sizes, and the kill-one-mid-burst failover row:
# SIGKILL one of two daemons with a batch in flight, every lane keeps
# its exact verdict through re-dispatch, the dead endpoint's breaker
# opens and re-closes after restart. Runs as part of `make tier1` (the
# 1/2/4 ladder writes BENCH_r21.json; the chaos matrix lives in
# tests/test_chaos_devd.py + tests/test_devd_shard.py).
shard-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_DEVD_SHARD_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_devd_shard.py

# Upgrade-at-height smoke, chip-free (~60-90 s): bench_upgrade.py's
# reduced pass — ONE 4-process localnet rolling-upgraded across the
# genesis commit-format flip (laggard SIGKILLed before H, survivors
# cross without missing a height, laggard catches up through both
# formats, per-height byte identity both sides of H, upgrade_* scrape
# asserts, zero schedule refusals). Runs as part of `make tier1`; the
# full bench adds the wire/verify A-B at 100/400 validators and the
# flip-stall row, and writes BENCH_r22.json (docs/upgrade.md).
upgrade-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_UPGRADE_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_upgrade.py

# Overload-control smoke, chip-free (~90 s): bench_overload.py's reduced
# pass — ONE 4-process localnet where node 0 is flooded with bulk writes,
# hot reads, and two deliberately-slow WS subscribers while the scenario
# asserts consensus cadence stays within 1.5x the unloaded baseline,
# sheds are scrape-visible (rpc_shed_total / mempool_lane_full_total /
# ws_evictions_total), a priority probe commits ahead of a bulk marker
# submitted before it, the ladder transition lands in the flight ring,
# and per-height byte identity holds. Runs as part of `make tier1`; the
# full bench adds an n=6 row and writes BENCH_r23.json (docs/serving.md).
overload-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_OVERLOAD_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_overload.py

# Read-replica smoke, chip-free (~60-90 s): bench_replica.py's reduced
# pass — the replica_flood scenario on ONE 4-process localnet with two
# verified replica processes (plus one TAMPERING one) behind node 0. A
# hot verified-read flood + WS subscribers land on the replicas while
# the scenario asserts the validator's commit cadence stays flat,
# replica-served blocks are byte-identical to the validator's, the
# replica_* scrape rows move with zero proof failures, and a verifying
# client rejects 100% of reads from the tampered replica. Runs as part
# of `make tier1`; the full bench adds the 1/2/4-replica serving ladder
# and writes BENCH_r24.json (docs/serving.md § Read replicas).
replica-smoke:
	JAX_PLATFORMS=cpu TENDERMINT_TPU_PLATFORM=cpu BENCH_REPLICA_SMOKE=1 timeout -k 10 300 $(PY) benches/bench_replica.py

test_race:
	$(PY) -m pytest tests/test_race.py -q

test_integrations:
	$(PY) test/p2p/scenarios.py

test_slow:
	$(PY) -m pytest tests/ -q -m slow

native:
	$(MAKE) -C native

.PHONY: test test_race test_integrations test_slow native tier1 bench-smoke hash-stream-smoke chaos-smoke wal-torture-smoke statesync-smoke statetree-smoke metrics-smoke net-chaos-smoke wan-smoke pipeline-smoke fleet-smoke committee-smoke txtrace-smoke retention-smoke localnet-smoke shard-smoke upgrade-smoke overload-smoke replica-smoke
