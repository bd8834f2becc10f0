"""Deployment `ycsb_net`: `validator_net`'s four validators, daemon and
window under YCSB core workload A (traffic `ycsb_core`): every record
loaded before the window, one Ed25519 key a record, more keys than the
verifier's table pool has slots.

Set-up, each step through a path the program has:
- the load: a chain of the seed's inserts under one loader key
  (`harness/ycsb_load.py`), installed in every home and replayed by every
  validator at boot;
- the pool's history: updates of the run's zipfian shown to the daemon
  through its socket until the pool is full and evicting (`fill_pool`);
- against a daemon whose `status` announces no miss programs the run
  ends here, in seconds, with no result.

A traced run traces four calls of the harness's own early in set-up
(`traced_stretch`: a full ladder program, a full table build with its
pool update, the comb program at 8 and at 256 lanes), not the end of the
window as the other cells do. They are the four programs the window
runs, at both ends of its widths, and nothing else: a table build is some 400,000 device events in a trace and a
ladder 80,000 where a comb program is 22,000, the launcher's writing of
them takes minutes, not the other cells' half a minute (my chip runs, PR
35: a stretch at the window's end with one build in it was still being
written when `run.py`'s 345 s watchdog fired, twice), and from here it
runs beside the replays, the history and the window instead of after
them. A program's device time does not depend on when it runs; the
window's busy share carries the stretch's times over the window's calls
by what the daemon's counters and records say each ran.

The timed path is the public RPC: `broadcast_tx_commit` for an update
(the sample of the commit latencies), `abci_query` with a proof for a
read. What it produced is judged after the window (`judge_live`,
`judge_pool`) against `reference/ycsb_ref.py` (the draw, the store, the
values a read may return), `reference/kv_ref.py` and
`reference/ed25519_ref.py` (values and verdicts) and
`reference/pool_lru_ref.py` (the pool, from the daemon's log of batches);
`validator_net.judge`'s comparisons are taken whole.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import subprocess
import sys
import threading
import time
from collections import Counter

from harness import device, procs, rpc, ycsb, ycsb_load
from harness.chain import derive
from harness.observe import Observations, quantile, sleep_until
from reference import kv_ref, pool_lru_ref, ycsb_ref
from scenarios import validator_net as vn

BOOT_LIMIT_S = 200.0
POOL_COUNTERS = ("lanes_hit", "lanes_first_sight", "lanes_built", "builds",
                 "build_keys", "evictions", "ladders")


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    t_setup = time.time()
    run_dir, seed = ctx.run_dir, ctx.seed
    native_s = procs.build_native()
    daemon = procs.Daemon(run_dir, cfg["daemon"], control=ctx.control,
                          accept_cpu=ctx.rehearsal)

    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    n = int(cfg["validators"])
    recordcount = int(cfg["recordcount"])
    theta = float(mix["zipfian_constant"])
    chain_id = f"perfbench-{cfg['name']}"
    pvs = vn._validators(seed, n)
    genesis = GenesisDoc(
        genesis_time_ns=ycsb_load.GENESIS_TIME_NS, chain_id=chain_id,
        validators=[GenesisValidator(pv.get_pub_key(), 10, f"node{i}")
                    for i, pv in enumerate(pvs)])
    genesis.validate_and_complete()
    ports = procs.free_ports(2 * n)
    nodes = []
    for i, pv in enumerate(pvs):
        home = os.path.join(run_dir, f"node{i}")
        sets = {"base": {"chain_id": chain_id, "moniker": f"node{i}",
                         "proxy_app": cfg["app"], **cfg.get("base", {})},
                "consensus": dict(cfg["consensus"])}
        procs.write_home(home, genesis, pv, sets)
        nodes.append(procs.Node(home, i, ports[2 * i], ports[2 * i + 1]))
    addrs = [nd.rpc_addr for nd in nodes]

    # the load, built and installed by a thread of its own beside the
    # daemon's claim
    made: dict = {}

    def load():
        try:
            made.update(ycsb_load.build_loaded_chain(
                os.path.join(run_dir, "load"), seed=seed, genesis=genesis,
                pvs=pvs, recordcount=recordcount,
                txs_per_block=int(cfg["load"]["txs_per_block"]),
                n_workers=max(1, min(6, (os.cpu_count() or 2) - 3))))
            for nd in nodes:
                ycsb_load.install(made, nd.home)
        except BaseException as exc:  # noqa: BLE001 — re-raised by the run
            made["error"] = exc

    loader = threading.Thread(target=load, name="ycsb-load")
    loader.start()

    gen_files = {k: os.path.join(run_dir, f"loadgen.{k}")
                 for k in ("params", "ready", "start", "window", "out", "log")}
    with open(gen_files["params"], "w") as f:
        json.dump({
            "seed": seed, "seconds": ctx.seconds,
            "rate_per_s": mix["rate_per_s"], "arrivals": mix["arrivals"],
            "lead_in_s": mix["lead_in_s"], "recordcount": recordcount,
            "read_share": mix["read_share"], "zipfian_constant": theta,
            "request_timeout_s": mix["request_timeout_s"],
            "targets": [list(a) for a in addrs],
            "root": procs.ROOT, "bench_dir": procs.BENCH,
            "ready_file": gen_files["ready"], "start_file": gen_files["start"],
            "window_file": gen_files["window"], "out_file": gen_files["out"],
        }, f)
    gen = subprocess.Popen(
        [sys.executable, os.path.join(procs.HERE, "ycsb_loadgen.py"),
         gen_files["params"]],
        env=procs.base_env(), cwd=procs.ROOT,
        stdout=open(gen_files["log"], "ab"), stderr=subprocess.STDOUT,
        start_new_session=True)
    procs._children.append(gen)

    marks = {"prepared": time.time() - t_setup}
    held = daemon.wait_held(time.time() + 900)
    marks["daemon_held"] = time.time() - t_setup
    dev = device.check_device(daemon, held, int(ctx.workload["chips"]),
                              ctx.rehearsal)
    miss_programs = (held.get("claim") or {}).get("miss_programs_s")
    if not miss_programs:
        raise procs.HarnessError(
            "this program's daemon cannot serve an open population: its "
            "status announces no miss programs (claim.miss_programs_s), so "
            "every distinct count of new keys would compile inside the run")

    # the validators' keys and the loader's resident, the widest bucket
    # (a replayed block's) compiled, before the nodes start; the other
    # widths while they replay
    dcfg = cfg["daemon"]
    items = _warm_items(seed, pvs, max(dcfg["warm_buckets"]))
    warm = device.warm_tables(daemon, items, max(dcfg["warm_buckets"]),
                              int(dcfg.get("warm_passes", 2)))
    warm.update(device.warm_buckets(daemon, items, dcfg["warm_buckets"][:1]))
    trace = traced_stretch(ctx, daemon, items, dcfg) if ctx.trace else None
    loader.join(timeout=600)
    if loader.is_alive() or "error" in made:
        raise procs.HarnessError(f"the load was not built: {made.get('error')}")
    marks["loaded_chain_installed"] = time.time() - t_setup
    node_env = {**cfg["node_env"], "TENDERMINT_DEVD_SOCK": daemon.sock}
    t_boot = time.time()
    for nd in nodes:
        nd.start([f"127.0.0.1:{m.p2p_port}" for m in nodes[:nd.index]], node_env)
    warm.update(device.warm_buckets(daemon, items, dcfg["warm_buckets"][:-1]))
    warm["total"] = round(sum(warm.values()), 3)
    marks["warmed"] = time.time() - t_setup
    history = fill_pool(daemon, seed, recordcount, theta,
                        int(cfg["pool_history"]["lanes_a_batch"]))
    marks["pool_full"] = time.time() - t_setup

    def alive():
        for nd in nodes:
            nd.check_alive()

    if not rpc.wait_heights(addrs, made["blocks"] + 2,
                            time.time() + BOOT_LIMIT_S, alive):
        raise procs.HarnessError(
            f"the nodes did not replay the load and reach height "
            f"{made['blocks'] + 2}: " + procs.tail(nodes[0].log))
    replay_s = time.time() - t_boot
    marks["loaded_and_live"] = time.time() - t_setup
    vn._wait_file(gen_files["ready"], 120, gen)
    status0 = daemon.status()
    open(gen_files["start"], "w").close()
    vn._wait_file(gen_files["window"], 30, gen)
    with open(gen_files["window"]) as f:
        win = json.load(f)
    open_wall, close_wall = win["open_wall"], win["close_wall"]
    setup_s = open_wall - t_setup

    # -- the window ------------------------------------------------------
    obs = Observations(window_s=ctx.seconds, open_wall=open_wall)
    sleep_until(open_wall)
    snap0, pool0 = vn._snapshot(addrs, daemon), daemon.status()["comb_pool"]
    sleep_until(close_wall)
    snap1, pool1 = vn._snapshot(addrs, daemon), daemon.status()["comb_pool"]
    # every span since set-up began: the traced stretch lies there
    launcher = daemon.request("snapshot", since_ns=int(t_setup * 1e9))
    alive()

    # -- after the window: wait for every answer, then judge ---------------
    try:
        gen.wait(timeout=float(mix["request_timeout_s"]) + 30)
    except subprocess.TimeoutExpired:
        raise procs.HarnessError("the generator did not finish: "
                                 + procs.tail(gen_files["log"]))
    if gen.returncode != 0:
        raise procs.HarnessError("the generator failed: "
                                 + procs.tail(gen_files["log"]))
    with open(gen_files["out"]) as f:
        lg = json.load(f)
    dev_after = daemon.request("device")
    k0 = lg["lead_in_operations"]
    idx = list(range(k0, len(lg["due"])))
    updates = [i for i in idx if lg["kind"][i] == "update"]
    reads = [i for i in idx if lg["kind"][i] == "read"]
    lat = [1000.0 * (lg["done"][i] - lg["due"][i]) for i in updates if lg["ok"][i]]
    read_lat = [1000.0 * (lg["done"][i] - lg["due"][i]) for i in reads if lg["ok"][i]]
    failed = sum(1 for i in idx if not lg["ok"][i])
    unanswered = sum(1 for i in idx
                     if lg["done"][i] is None or "Timeout" in (lg["err"][i] or ""))
    obs.series["commit_latency_ms"] = lat
    obs.series["read_latency_ms"] = read_lat
    obs.series["generator_late_ms"] = [
        1000.0 * (lg["sent"][i] - lg["due"][i]) for i in idx]
    top = max([lg["height"][i] for i in updates if lg["ok"][i]] or [0])
    if not rpc.wait_heights(addrs, top + 1, time.time() + 60, alive):
        raise procs.HarnessError(f"not every node reached height {top + 1}")
    traces = rpc.call(addrs[0], "consensus_trace", {"last": 128})["traces"]
    in_win = [t for t in traces
              if open_wall <= t.get("started_at", 0) < close_wall]
    tc = float(cfg["consensus"]["timeout_commit"])
    obs.series["height_work_ms"] = [1000.0 * (t["wall_s"] - tc) for t in in_win]
    obs.series["height_interval_ms"] = [1000.0 * t["wall_s"] for t in in_win]
    obs.series["height_rounds_over_zero"] = [
        1.0 for t in in_win if int(t.get("rounds", 1)) > 1]
    obs.scalars["heights_in_window"] = float(len(in_win))
    for key in snap0["sum"]:
        obs.counters["nodes." + key] = (snap0["sum"][key], snap1["sum"][key])
    for key in ("tpu_sigs", "cpu_sigs"):
        obs.counters["daemon." + key] = (snap0["daemon"][key], snap1["daemon"][key])
    for key in POOL_COUNTERS:
        obs.counters["pool." + key] = (pool0.get(key, 0), pool1.get(key, 0))
    usable = max(1, int(pool1["capacity"]) - 1)
    obs.series["pool_resident_share"] = [100.0 * pool1["resident_keys"] / usable]
    obs.set_launcher(launcher, open_wall, close_wall)
    obs.trace = trace

    comparisons, jnotes = judge_live(ctx, cfg, mix, addrs, lg, top, unanswered,
                                     status0, daemon, history, pool0, pool1,
                                     made["blocks"] + 1)
    if trace:
        ctx.finish_trace(daemon, trace)
    metrics_e2e = {}
    if lat:
        metrics_e2e["commit_latency_p50_ms"] = quantile(lat, 0.50)
        metrics_e2e["commit_latency_p95_ms"] = quantile(lat, 0.95)
    if read_lat:
        metrics_e2e["read_latency_p50_ms"] = quantile(read_lat, 0.50)
    metrics_e2e["setup_s"] = setup_s
    codes = []
    for nd in nodes:
        nd.proc.terminate()
    for nd in nodes:
        try:
            codes.append(nd.proc.wait(timeout=30))
        except subprocess.TimeoutExpired:
            codes.append(None)
    daemon_code = daemon.shutdown()
    pool_cmp, pnotes = judge_pool(daemon, jnotes.pop("forged_pubkeys"))
    delta = {k: pool1.get(k, 0) - pool0.get(k, 0) for k in POOL_COUNTERS}
    return {
        "attempted": len(idx), "failed": failed,
        "end_to_end": metrics_e2e, "obs": obs,
        "comparisons": comparisons + pool_cmp,
        "device": {**dev, "memory_peak_bytes": dev_after["memory_peak_bytes"]},
        "notes": {"native_build_s": round(native_s, 2), "warm": warm,
                  "miss_programs_s": miss_programs,
                  "load": {k: made[k] for k in ("blocks", "records", "signing_s",
                                                "build_s", "store_bytes")},
                  "load_s": made["build_s"], "replay_s": round(replay_s, 2),
                  "pool_history": history["notes"],
                  "updates": len(updates), "reads": len(reads),
                  "read_latency_ms": {"p50": quantile(read_lat, 0.5),
                                      "p95": quantile(read_lat, 0.95)}
                  if read_lat else {},
                  "pool_at_close": pool1, "pool_in_window": delta,
                  **jnotes, **pnotes,
                  "heights_in_window": len(in_win), "top_height": top,
                  "node_exit_codes": codes, "daemon_exit_code": daemon_code,
                  "setup_marks_s": {k: round(v, 2) for k, v in marks.items()},
                  "compiles_in_window": len(obs.compiles_in_window),
                  "height_wall_ms": [round(1000 * t["wall_s"]) for t in in_win][::-1],
                  "batch_lanes_in_window": obs.lanes_histogram(),
                  "trace": {k: v for k, v in (trace or {}).items()
                            if k not in ("extracted", "dir")},
                  "claim_s": held.get("claim", {}).get("claim_s")},
    }


def _warm_items(seed: int, pvs, top: int) -> list:
    """One valid lane a validator key, the rest under the loader's key:
    the keys a replayed block shows the daemon."""
    items = []
    for i, pv in enumerate(pvs):
        msg = b"warm-val-%d" % i
        items.append((pv.get_pub_key().raw, msg, pv.priv_key.sign(msg).raw))
    pub, sign = ycsb.make_keypair()(ycsb.loader_secret(seed))
    for k in range(top - len(items)):
        msg = b"warm-loader-%d" % k
        items.append((pub, msg, sign(msg)))
    return items


class Signers:
    """The records' keys, made when first used (a pair costs 0.1 ms;
    the run uses under half of them)."""

    def __init__(self, seed: int):
        self.seed, self.keypair, self.held = seed, ycsb.make_keypair(), {}

    def of(self, record: int):
        pair = self.held.get(record)
        if pair is None:
            pair = self.held[record] = self.keypair(
                ycsb.record_secret(self.seed, record))
        return pair


def fill_pool(daemon, seed: int, recordcount: int, theta: float,
              lanes: int) -> dict:
    """The pool's history: batches of `lanes` updates of the run's
    zipfian, each shown twice as a deployment's update is shown eight
    times (first its never-shown keys alone: their first sight; then the
    whole batch: their build, and hits for the rest), until every slot
    holds a key and one has been evicted. Verifier traffic only."""
    t0 = time.time()
    signers, draw = Signers(seed), ycsb.draw_history(seed, recordcount, theta)
    last_shown: dict[int, int] = {}
    batches = sent = 0
    client = daemon.client(io_timeout=600.0)
    try:
        while True:
            recs = [next(draw) for _ in range(lanes)]
            items = []
            for j, r in enumerate(recs):
                pub, sign = signers.of(r)
                msg = b"history-%d-%d" % (batches, j)
                items.append((pub, msg, sign(msg)))
            new = [it for it, r in zip(items, recs) if r not in last_shown]
            for part in (new, items):
                if part and not all(client.verify_batch(part)):
                    raise procs.HarnessError("a history lane was rejected")
                sent += len(part)
            for r in recs:
                last_shown[r] = batches
            batches += 1
            if batches % 8 == 0:
                pool = daemon.status()["comb_pool"]
                if pool["resident_keys"] >= pool["capacity"] - 1 \
                        and pool["evictions"] > 0:
                    break
            if batches > 20000:
                raise procs.HarnessError("the pool did not fill")
    finally:
        client.close()
    return {"last_shown": last_shown,
            "notes": {"batches": batches, "lanes": sent,
                      "records": len(last_shown),
                      "seconds": round(time.time() - t0, 2),
                      "resident_keys": pool["resident_keys"],
                      "evictions": pool["evictions"]}}


def traced_stretch(ctx, daemon, items, dcfg) -> dict:
    """Four calls of the harness's own, traced (the module's docstring
    says why here and not at the window's end): 128 keys nobody has shown
    (their first sight: one full ladder program), the same again (their
    second: one full table build, the pool update, a comb program), then
    the 256-lane warm-up batch and its first 8 lanes once more (every
    key resident: the comb program alone, at both ends of its widths).
    Every program ran once before, untraced: these are not first runs."""
    keypair = ycsb.make_keypair()
    probe = []
    # one full miss bucket (a rehearsal's small pool: what it can hold)
    usable = int(daemon.status()["comb_pool"]["capacity"]) - 1
    for k in range(min(128, usable // 2)):
        pub, sign = keypair(derive(ctx.seed, "probe", k))
        probe.append((pub, b"probe-%d" % k, sign(b"probe-%d" % k)))
    trace = {"dir": os.path.join(ctx.run_dir, "trace"),
             "widths": list(dcfg["warm_buckets"]), "distinct_keys": 0}
    trace["start_wall_ns"] = daemon.request(
        "start_trace", dir=trace["dir"])["start_wall_ns"]
    try:
        for part in (probe, probe, items, items[:8]):
            if not all(device.send(daemon, part)):
                raise procs.HarnessError("a lane of the traced stretch was rejected")
    finally:
        trace["pending"] = daemon.post("stop_trace")
    return trace


def _sha(value: bytes) -> str:
    return hashlib.sha256(value).hexdigest()


# A node under load turns a write away for the moment and says so: its
# mempool lane full or its overload ladder at shed-writes (ABCI code 6,
# `mempool_lane_full:<lane>`, `mempool_shed_writes:<lane>`), or its
# signature gate's backlog full (code 3, `signature gate saturated;
# retry`). Such an answer says "later", not "invalid".
SHED_ANSWERS = ((6, ("mempool_lane_full:", "mempool_shed_writes:")),
                (3, ("signature gate saturated",)))
DRAIN_LIMIT_S = 60.0


def answer_kind(ok: bool, err: str | None) -> str:
    """What an update's answer says: `acked` (both codes 0), `shed`
    (turned away under load, see SHED_ANSWERS), `refused` (a CheckTx or
    DeliverTx code that judges the write) or `open` (no verdict: an RPC
    error such as a time-out, or no answer), whose fate the chain says."""
    if ok:
        return "acked"
    if not (err or "").startswith("{"):
        return "open"
    check = json.loads(err)["check_tx"]
    for code, logs in SHED_ANSWERS:
        if check["code"] == code and check["log"].startswith(logs):
            return "shed"
    return "refused"


def _chain_places(addrs, first_height: int) -> dict[str, tuple[int, int]]:
    """Every tx of the chain from `first_height` on, at its (height,
    place in the block), once every node's mempool has drained (a write
    whose answer timed out may still be committed; a minute at most)."""
    deadline = time.time() + DRAIN_LIMIT_S
    while time.time() < deadline:
        try:
            if all(int(rpc.call(a, "num_unconfirmed_txs")["n_txs"]) == 0
                   for a in addrs):
                break
        except (OSError, rpc.RPCFailure):
            pass                                   # a shed read: ask again
        time.sleep(0.2)
    head = min(rpc.height(a) for a in addrs)
    places: dict[str, tuple[int, int]] = {}
    for h in range(first_height, head + 1):
        blk = rpc.call(addrs[0], "block", {"height": h}, timeout=30)["block"]
        for k, t in enumerate(blk["data"]["txs"] or []):
            places.setdefault(t.upper(), (h, k))
    return places


def judge_live(ctx, cfg, mix, addrs, lg, top, unanswered, status0, daemon,
               history, pool0, pool1, first_height) -> tuple[list, dict]:
    """Every number compared while the nodes and the daemon still answer,
    beside its limit (all exact: every limit 0), and the notes."""
    seed, recordcount = ctx.seed, int(cfg["recordcount"])
    n_ops = len(lg["kind"])
    k0 = lg["lead_in_operations"]
    rng = random.Random(seed ^ 0x5EED)

    # 1. the generator sent what the seed draws: every operation's kind
    #    and record, every update's payload
    want = ycsb_ref.operations(seed, n_ops, recordcount,
                               float(mix["read_share"]),
                               float(mix["zipfian_constant"]))
    off_draw = sum(1 for i in range(n_ops)
                   if (lg["kind"][i], lg["record"][i]) != want[i])
    all_updates = [i for i in range(n_ops) if lg["kind"][i] == "update"]
    for i in all_updates:
        tx = bytes.fromhex(lg["tx"][i])
        r = want[i][1]
        if tx[kv_ref.SIG_TX_OVERHEAD:] != ycsb_ref.key_of(r) + b"=" \
                + ycsb_ref.value_of(seed, r, i + 1):
            off_draw += 1

    # 2. the chain's order of the updates it holds, lead-in included: the
    #    acknowledged ones at the height each names and their place in
    #    that block, and those whose answer gave no verdict that the chain
    #    took all the same, where it took them (acknowledged by no node)
    said = {i: answer_kind(lg["ok"][i], lg["err"][i]) for i in all_updates}
    chain = _chain_places(addrs, first_height)
    acked = [i for i in all_updates if said[i] == "acked"]
    height = {i: lg["height"][i] for i in acked}
    place = {i: chain[lg["tx"][i].upper()][1]
             if chain.get(lg["tx"][i].upper(), (None,))[0] == height[i] else -1
             for i in acked}
    late = [i for i in all_updates
            if said[i] == "open" and lg["tx"][i].upper() in chain]
    for i in late:
        height[i], place[i] = chain[lg["tx"][i].upper()]
    # a write turned away under load is one the chain does not hold
    shed_in_chain = sum(1 for i in all_updates
                        if said[i] == "shed" and lg["tx"][i].upper() in chain)
    order = sorted(acked + late, key=lambda i: (height[i], place[i]))
    store = ycsb_ref.Store(seed, recordcount)
    writes: dict[int, tuple] = {}
    for i in order:
        store.acknowledge(lg["record"][i], i + 1, height[i], place[i])
        writes[i + 1] = ((lg["node"][i], lg["sent"][i], lg["done"][i])
                         if said[i] == "acked" else (-1, lg["sent"][i], float("inf")))

    # 3. every answered read of the window: a loaded record is there, the
    #    value is one the reference allows, and it is what the record
    #    held at the height the read names
    found_none = reads_off = reads_found = 0
    window_reads = [i for i in range(k0, n_ops)
                    if lg["kind"][i] == "read" and lg["ok"][i]]
    for i in window_reads:
        length, digest = lg["got"][i]
        r = lg["record"][i]
        if length == 0:
            found_none += 1
            continue
        reads_found += 1
        may = ycsb_ref.versions_a_read_may_return(
            store.history(r), writes, lg["node"][i], lg["sent"][i], lg["done"][i])
        if digest not in {_sha(store.value(r, v)) for v in may} \
                or digest != _sha(store.at_height(r, lg["height"][i])):
            reads_off += 1

    # 4. validator_net's comparisons over the acknowledged updates, the
    #    lead-in's with them, in the chain's order (its reference applies
    #    them in the order given); its forged writes are replaced by this
    #    cell's twelve
    view = {"ok": [True] * len(order),
            "key": [ycsb_ref.key_of(lg["record"][i]).hex() for i in order],
            "value": [bytes.fromhex(lg["tx"][i])[kv_ref.SIG_TX_OVERHEAD:]
                      .split(b"=", 1)[1].hex() for i in order],
            "tx": [lg["tx"][i] for i in order],
            "height": [height[i] for i in order]}
    ten = vn.judge(ctx, cfg, {**mix, "forged_writes": 0}, addrs, view,
                   list(range(len(order))), top, unanswered, status0, daemon)
    ten = [c for c in ten if c[0] not in ("forged_writes_accepted",
                                          "reference_verdict_disagreements")]

    # 5. valid updates refused (one shed under load is a failed
    #    operation, not a verdict); the sampled lanes' verdicts and owners
    #    by the plain reference
    refused = sum(1 for i in all_updates if said[i] == "refused")
    disagreements = 0
    window_acked = [i for i in acked if i >= k0]
    sample = rng.sample(window_acked, min(len(window_acked),
                                          int(mix["readback_sample"])))
    for i in sample:
        tx = bytes.fromhex(lg["tx"][i])
        if not kv_ref.tx_valid(tx):
            disagreements += 1
        if tx[:32] != ycsb_ref.owner_key(seed, lg["record"][i]):
            off_draw += 1

    # 6. forged updates: four under a key that is resident, four under
    #    one that was evicted, four under one nobody has shown
    #    (the four records whose updates the chain took last: every node
    #    verified them a block ago, whatever the pool's size; the four
    #    records the pool's history showed longest ago that nothing has
    #    touched since)
    hot = list(dict.fromkeys(lg["record"][i] for i in reversed(order)))[:4]
    drawn = {r for _k, r in want}
    last_shown = history["last_shown"]
    cold = sorted((r for r in last_shown if r not in drawn),
                  key=last_shown.get)[:4]
    signers = Signers(seed)
    keypair = ycsb.make_keypair()
    accepted = {"resident": 0, "evicted": 0, "never_seen": 0}
    forged_pubkeys = {k: [] for k in accepted}
    n_forged = 0
    for kind, owners in (("resident", hot), ("evicted", cold),
                         ("never_seen", [None] * 4)):
        for k, r in enumerate(owners):
            pub, sign = signers.of(r) if r is not None else \
                keypair(derive(seed, "forger", k))
            target = r if r is not None else hot[k % len(hot)]
            payload = ycsb_ref.key_of(target) + b"=forged-%d-%s" % (
                k, kind.encode())
            tx = bytearray(pub + sign(payload) + payload)
            if k % 2 == 0:
                tx[32 + 5] ^= 0x40                       # the signature
            else:
                tx[-1] ^= 0x01                           # the message
            tx = bytes(tx)
            n_forged += 1
            forged_pubkeys[kind].append(pub.hex())
            if kv_ref.tx_valid(tx):
                disagreements += 1
            res = rpc.call(addrs[k % len(addrs)], "broadcast_tx_commit",
                           {"tx": tx.hex()}, timeout=30)
            if (res.get("check_tx") or {}).get("code", 0) == 0:
                accepted[kind] += 1

    delta = {k: pool1.get(k, 0) - pool0.get(k, 0) for k in POOL_COUNTERS}
    quiet = 0 if (delta["lanes_first_sight"] > 0 and delta["builds"] > 0
                  and delta["evictions"] > 0) else 1
    comparisons = ten + [
        ("ops_differing_from_reference_draw", off_draw, 0),
        ("reads_differing_from_reference", reads_off, 0),
        ("reads_of_a_loaded_record_that_found_none", found_none, 0),
        ("valid_writes_refused", refused, 0),
        ("shed_writes_in_chain", shed_in_chain, 0),
        ("forged_writes_accepted.resident", accepted["resident"], 0),
        ("forged_writes_accepted.evicted", accepted["evicted"], 0),
        ("forged_writes_accepted.never_seen", accepted["never_seen"], 0),
        ("forged_writes_not_sent", int(mix["forged_writes"]) - n_forged, 0),
        ("reference_verdict_disagreements", disagreements, 0),
        ("window_without_a_miss_a_build_and_an_eviction", quiet, 0),
    ]
    return comparisons, {
        "reads_that_found_a_record": reads_found,
        "reads_answered": len(window_reads),
        "forged_writes_accepted": sum(accepted.values()),
        "forged_pubkeys": forged_pubkeys,
        "updates_by_answer": dict(Counter(said.values())),
        "updates_committed_unacknowledged": len(late),
        "updates_shed_by_log": dict(Counter(
            json.loads(lg["err"][i])["check_tx"]["log"]
            for i in all_updates if said[i] == "shed")),
    }


def judge_pool(daemon, forged_pubkeys: dict) -> tuple[list, dict]:
    """The daemon has stopped and written its pool's log of batches:
    every lane's route and every eviction against the plain model."""
    path = daemon.sock[:-len(".sock")] + ".pool.jsonl"
    if not os.path.exists(path):
        raise procs.HarnessError(f"the daemon left no pool log: {path}")
    with open(path) as f:
        header = json.loads(f.readline())
        batches = [json.loads(line) for line in f if line.strip()]
    out = pool_lru_ref.replay(header, batches)
    # where the forged updates' keys stood when they were first shown
    number = {k: n + 1 for n, k in enumerate(header["keys"])}
    # (a refused update reaches the verifier once, at one node's CheckTx:
    # it is its key's LAST lane in the log, and a never-shown key's only)
    wanted = {number.get(p): kind for kind, pubs in forged_pubkeys.items()
              for p in pubs}
    names = header["routes"]
    last_route: dict[int, str] = {}
    for b in batches:
        for n, c in zip(b["k"], b["r"]):
            if n in wanted:
                last_route[n] = names[int(c)]
    expect = {"resident": "hit", "evicted": "rebuilt",
              "never_seen": "first_sight"}
    not_of_kind = sum(1 for n, kind in wanted.items()
                      if n is None or last_route.get(n) != expect[kind])
    return [
        ("lanes_routed_unlike_reference", out["lanes_routed_unlike_reference"]
         + (0 if header["whole"] else 1), 0),
        ("pool_resident_over_capacity", out["resident_over_capacity"], 0),
        ("forged_writes_not_of_their_kind", not_of_kind, 0),
    ], {"pool_model": {k: out[k] for k in ("counts", "evictions", "resident",
                                           "resident_max")},
        "forged_routes": {kind: [last_route.get(number.get(p)) for p in pubs]
                          for kind, pubs in forged_pubkeys.items()},
        "pool_log_batches": len(batches)}
