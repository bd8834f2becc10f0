"""Deployment `burst_net`: `validator_net`'s four validators, daemon and
generator process, under upstream v0.11.0's own limits written out (the
configuration's `upstream_limits`), and traffic `burst_writes`: a lead-in
at a steady rate, then the configuration's `burst_writes` signed writes
in the traffic's `bursts_at_s` equal parts, each part due at once at its
offset from the window's open, handed over through `broadcast_tx_sync`
on bounded connections (`harness/burst_loadgen.py`).

A write's commit instant is the arrival of the `NewBlock` event, from the
node it was sent to, of the block that holds it; its commit latency runs
from its part's due instant to there. What the burst produced is judged
after the window (`judge`) against `reference/burst_ref.py` (the writes,
which are forged, their verdicts, the values they leave) and
`validator_net.judge`'s comparisons over the committed writes.

A traced run traces a stretch INSIDE the drain, from the instant node 0
commits a block of several parts (`trace_the_drain`); the notes say
how many of its calls were of 1,024 lanes or more.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

from harness import device, procs, rpc
from harness.observe import Observations, quantile, sleep_until
from reference import burst_ref
from scenarios import validator_net as vn

BOOT_LIMIT_S = 150.0
# a node sends a batch this wide or wider down the streamed protocol
# (TENDERMINT_DEVD_STREAM_MIN's default): the harness warms those widths
# the same way
STREAM_MIN = 256
WIDE_LANES = 1024
# the traced stretch starts when node 0 commits (round step 8) a proposal
# of this many 64 KB parts or more: a block of over a thousand writes,
# whose apply streams every node's block call next
WIDE_PARTS = 3
COMMIT_STEP = 8


def check_limits(limits: dict) -> None:
    """The upstream limits this program fixes rather than configures are
    the ones the configuration states, or the run has no result."""
    from tendermint_tpu.mempool import mempool
    from tendermint_tpu.types.params import ConsensusParams

    fixed = {"block_part_size_bytes":
             ConsensusParams().block_gossip.block_part_size_bytes,
             "mempool_cache_size": mempool.CACHE_SIZE}
    for key, value in fixed.items():
        if value != limits[key]:
            raise procs.HarnessError(
                f"the program's {key} is {value}, the configuration's "
                f"{limits[key]}")


def gate_counters(addrs) -> dict:
    """The signature gates' counters over all nodes (GET /debug/queues);
    a program whose gate does not count them gives none."""
    import http.client

    total: dict[str, float] = {}
    for a in addrs:
        conn = http.client.HTTPConnection(a[0], a[1], timeout=10)
        try:
            conn.request("GET", "/debug/queues")
            mp = json.loads(conn.getresponse().read()).get("mempool") or {}
        finally:
            conn.close()
        for k in ("sig_gate_batches", "sig_gate_lanes", "sig_gate_dropped"):
            if k in mp:
                total[k] = total.get(k, 0.0) + float(mp[k])
    return total


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    t_setup = time.time()
    run_dir, seed = ctx.run_dir, ctx.seed
    limits = cfg["upstream_limits"]
    check_limits(limits)
    native_s = procs.build_native()
    daemon = procs.Daemon(run_dir, cfg["daemon"], control=ctx.control,
                          accept_cpu=ctx.rehearsal)

    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    n = int(cfg["validators"])
    chain_id = f"perfbench-{cfg['name']}"
    pvs = vn._validators(seed, n)
    genesis = GenesisDoc(
        genesis_time_ns=time.time_ns(), chain_id=chain_id,
        validators=[GenesisValidator(pv.get_pub_key(), 10, f"node{i}")
                    for i, pv in enumerate(pvs)])
    genesis.validate_and_complete()
    ports = procs.free_ports(2 * n)
    nodes = []
    for i, pv in enumerate(pvs):
        home = os.path.join(run_dir, f"node{i}")
        sets = {"base": {"chain_id": chain_id, "moniker": f"node{i}",
                         "proxy_app": cfg["app"], **cfg.get("base", {})},
                "consensus": {**cfg["consensus"],
                              "max_block_size_txs": limits["max_block_size_txs"]},
                "p2p": {"send_rate": limits["send_rate"],
                        "recv_rate": limits["recv_rate"]},
                "rpc": {"max_connections": limits["rpc_max_connections"],
                        "max_inflight": limits["rpc_max_inflight"]}}
        procs.write_home(home, genesis, pv, sets)
        nodes.append(procs.Node(home, i, ports[2 * i], ports[2 * i + 1]))
    addrs = [nd.rpc_addr for nd in nodes]

    n_burst = int(cfg["burst_writes"])
    gen_files = {k: os.path.join(run_dir, f"loadgen.{k}")
                 for k in ("params", "ready", "start", "window", "out", "log")}
    with open(gen_files["params"], "w") as f:
        json.dump({
            "seed": seed, "seconds": ctx.seconds, "burst_writes": n_burst,
            "lead_in_s": mix["lead_in_s"],
            "lead_in_rate_per_s": mix["lead_in_rate_per_s"],
            "connections_per_node": mix["connections_per_node"],
            "bursts_at_s": mix["bursts_at_s"],
            "signers": mix["signers"],
            "request_timeout_s": mix["request_timeout_s"],
            "answer_after_close_s": mix["answer_after_close_s"],
            "targets": [list(a) for a in addrs], "bench_dir": procs.BENCH,
            "ready_file": gen_files["ready"], "start_file": gen_files["start"],
            "window_file": gen_files["window"], "out_file": gen_files["out"],
        }, f)
    gen = subprocess.Popen(
        [sys.executable, os.path.join(procs.HERE, "burst_loadgen.py"),
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
    # every key's table before the nodes start; then every width the
    # burst's programs can have, the wide ones streamed as a node sends
    # them (the same keys again: every lane of the widest is a hit)
    dcfg = cfg["daemon"]
    buckets = sorted(dcfg["warm_buckets"])
    items = vn._warm_items(seed, pvs, int(mix["signers"]))
    warm = device.warm_tables(daemon, items, max(buckets),
                              int(dcfg.get("warm_passes", 2)))
    node_env = {**cfg["node_env"], "TENDERMINT_DEVD_SOCK": daemon.sock}
    for nd in nodes:
        nd.start([f"127.0.0.1:{m.p2p_port}" for m in nodes[:nd.index]], node_env)
    wide = [items[k % len(items)] for k in range(max(buckets))]
    warm.update(device.warm_buckets(
        daemon, wide, [b for b in buckets if b < STREAM_MIN]))
    warm.update({k + "_streamed": v for k, v in device.warm_buckets(
        daemon, wide, [b for b in buckets if b >= STREAM_MIN],
        stream_chunk=max(buckets)).items()})
    warm["total"] = round(sum(warm.values()), 3)
    marks["warmed"] = time.time() - t_setup

    def alive():
        for nd in nodes:
            nd.check_alive()

    if not rpc.wait_heights(addrs, 2, time.time() + BOOT_LIMIT_S, alive):
        raise procs.HarnessError("the nodes did not reach height 2: "
                                 + procs.tail(nodes[0].log))
    marks["height_2"] = time.time() - t_setup
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
    h_open = rpc.height(addrs[0])
    snap0, gate0 = vn._snapshot(addrs, daemon), gate_counters(addrs)
    trace = None
    if ctx.trace:
        trace = trace_the_drain(ctx, daemon, addrs[0], open_wall)
        trace["widths"] = list(buckets)
        trace["distinct_keys"] = len(items)
    sleep_until(close_wall)
    snap1, gate1 = vn._snapshot(addrs, daemon), gate_counters(addrs)
    if trace:
        ctx.finish_trace(daemon, trace)
    launcher = daemon.request("snapshot", since_ns=int(open_wall * 1e9))
    alive()

    # -- after the window: the generator's answers, then the judge --------
    try:
        gen.wait(timeout=float(mix["answer_after_close_s"]) + 60)
    except subprocess.TimeoutExpired:
        raise procs.HarnessError("the generator did not finish: "
                                 + procs.tail(gen_files["log"]))
    if gen.returncode != 0:
        raise procs.HarnessError("the generator failed: "
                                 + procs.tail(gen_files["log"]))
    with open(gen_files["out"]) as f:
        lg = json.load(f)
    dev_after = daemon.request("device")
    k0 = lg["lead_in_writes"]
    forged = set(lg["forged"])
    burst = list(range(k0, len(lg["due"])))
    valid = [i for i in burst if i not in forged]
    committed = [i for i in valid
                 if lg["code"][i] == 0 and lg["committed"][i] is not None]
    lat = [1000.0 * (lg["committed"][i] - lg["due"][i]) for i in committed]
    failed = len(valid) - len(committed)
    unanswered = sum(1 for i in burst if lg["code"][i] is None) + sum(
        1 for i in valid if lg["code"][i] == 0 and lg["committed"][i] is None)
    # the burst's first due instant to its last commit instant, and each
    # part's own (its due instant to its writes' last commit)
    drain_s = max((lg["committed"][i] for i in committed), default=0.0) \
        - min((lg["due"][i] for i in burst), default=0.0)
    parts: dict[float, float] = {}
    for i in committed:
        parts[lg["due"][i]] = max(parts.get(lg["due"][i], 0.0),
                                  lg["committed"][i] - lg["due"][i])
    obs.series["commit_latency_ms"] = lat
    if drain_s > 0:
        obs.series["committed_writes_per_s"] = [len(committed) / drain_s]
    top = max([lg["height"][i] for i in committed] or [0])
    if not rpc.wait_heights(addrs, top + 1, time.time() + 60, alive):
        raise procs.HarnessError(f"not every node reached height {top + 1}")
    traces = rpc.call(addrs[0], "consensus_trace", {"last": 128})["traces"]
    in_win = [t for t in traces
              if open_wall <= t.get("started_at", 0) < close_wall]
    obs.series["height_interval_ms"] = [1000.0 * t["wall_s"] for t in in_win]
    obs.series["height_rounds_over_zero"] = [
        1.0 for t in in_win if int(t.get("rounds", 1)) > 1]
    obs.scalars["heights_in_window"] = float(len(in_win))
    for key in snap0["sum"]:
        obs.counters["nodes." + key] = (snap0["sum"][key], snap1["sum"][key])
    for key in ("tpu_sigs", "cpu_sigs"):
        obs.counters["daemon." + key] = (snap0["daemon"][key], snap1["daemon"][key])
    for key in gate0:
        if key in gate1:
            obs.counters["gate." + key] = (gate0[key], gate1[key])
    obs.set_launcher(launcher, open_wall, close_wall)
    obs.trace = trace
    wide_at = sorted(round(s0 / 1e9 - open_wall, 3) for s0, _s1, lanes
                     in obs.spans if lanes >= WIDE_LANES)
    if trace:
        trace["wide_calls"] = sum(
            1 for s0, s1, lanes in obs.spans if lanes >= WIDE_LANES
            and s0 >= trace["start_wall_ns"] and s1 <= trace["stop_wall_ns"])

    comparisons, jnotes = judge(ctx, cfg, mix, addrs, lg, committed, forged,
                                top, unanswered, status0, daemon, h_open, obs)
    metrics_e2e = {}
    if lat:
        metrics_e2e["commit_latency_p50_ms"] = quantile(lat, 0.50)
        metrics_e2e["commit_latency_p95_ms"] = quantile(lat, 0.95)
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
    refused = Counter((lg["err"][i] or "")[:60] for i in valid
                      if lg["code"][i] not in (0, None))
    return {
        "attempted": len(valid), "failed": failed,
        "end_to_end": metrics_e2e, "obs": obs, "comparisons": comparisons,
        "device": {**dev, "memory_peak_bytes": dev_after["memory_peak_bytes"]},
        "notes": {"native_build_s": round(native_s, 2), "warm": warm,
                  "burst_writes": n_burst, "forged_writes": len(forged),
                  "drain_s": round(drain_s, 3),
                  "drain_s_by_part": [round(v, 3) for _k, v in sorted(parts.items())],
                  "answered_by_s": round(max(
                      (lg["checked"][i] for i in burst
                       if lg["checked"][i] is not None), default=0.0), 3),
                  "refused_valid_writes": dict(refused),
                  "gate_in_window": {k: gate1[k] - gate0[k] for k in gate0
                                     if k in gate1},
                  "wide_programs_at_s": wide_at[:64],
                  "trace_began_at_s": round(trace["start_wall_ns"] / 1e9
                                            - open_wall, 3) if trace else None,
                  **jnotes,
                  "heights_in_window": len(in_win), "top_height": top,
                  "node_exit_codes": codes, "daemon_exit_code": daemon_code,
                  "setup_marks_s": {k: round(v, 2) for k, v in marks.items()},
                  "compiles_in_window": len(obs.compiles_in_window),
                  "compiles_at_s": [[round(c[0] / 1e9 - open_wall, 3),
                                     round(c[1], 3)]
                                    for c in obs.compiles_in_window],
                  "height_wall_ms": [round(1000 * t["wall_s"]) for t in in_win][::-1],
                  "height_rounds": [int(t.get("rounds", 1)) for t in in_win][::-1],
                  "batch_lanes_in_window": obs.lanes_histogram(),
                  "trace": {k: v for k, v in (trace or {}).items()
                            if k not in ("extracted", "dir")},
                  "claim_s": held.get("claim", {}).get("claim_s")},
    }


def trace_the_drain(ctx, daemon, addr0, open_wall: float) -> dict:
    """The traced stretch, inside the drain: from `trace_at_s` after the
    open, at the first instant node 0 commits a proposal of WIDE_PARTS
    parts or more (or, where none comes, 10 s before the close). The
    launcher ends a trace at its 12th verifier call, and the nodes' gate
    and vote calls run at tens a second in the drain, so the stretch may
    end before the block's calls come: `wide_calls` counts those it
    holds, and nothing of the harness's own is sent into it."""
    mix = ctx.traffic
    sleep_until(open_wall + float(mix["trace_at_s"]))
    give_up = open_wall + ctx.seconds - 10.0
    trace = {"dir": os.path.join(ctx.run_dir, "trace"), "trigger": None}
    while time.time() < give_up:
        try:
            rs = rpc.call(addr0, "dump_consensus_state", timeout=2)["round_state"]
            parts = int(rs["proposal"]["block_parts_header"]["total"])
        except (OSError, rpc.RPCFailure, KeyError, TypeError, ValueError):
            parts = 0    # a shed read, no proposal yet
        if parts >= WIDE_PARTS and int(rs["step"]) == COMMIT_STEP:
            trace["trigger"] = {"height": rs["height"], "parts": parts,
                                "at_s": round(time.time() - open_wall, 3)}
            break
        time.sleep(0.005)
    trace["start_wall_ns"] = daemon.request(
        "start_trace", dir=trace["dir"])["start_wall_ns"]
    try:
        sleep_until(trace["start_wall_ns"] / 1e9 + float(mix["trace_window_s"]))
    finally:
        trace["pending"] = daemon.post("stop_trace")
    return trace


def _tx_result(addr, tx_hash: str):
    try:
        return rpc.call(addr, "tx", {"hash": tx_hash}, timeout=30)
    except (OSError, rpc.RPCFailure):
        return None


def judge(ctx, cfg, mix, addrs, lg, committed, forged, top, unanswered,
          status0, daemon, h_open, obs) -> tuple[list, dict]:
    """Every number compared, beside its limit (all exact: every limit
    0), and the notes."""
    seed = ctx.seed
    k0 = lg["lead_in_writes"]
    burst = list(range(k0, len(lg["due"])))
    ref = burst_ref.Burst(seed, len(burst), int(mix["signers"]))

    # the generator sent what the seed draws: each write's signer and
    # payload, the forged ones where the reference puts them
    off_plan = sum(1 for i in burst
                   if not ref.tx_fits(i - k0, bytes.fromhex(lg["tx"][i])))
    off_plan += len({i - k0 for i in forged} ^ set(ref.forged))

    # (a) the chain's blocks on node 0, from the open to the head: every
    #     valid burst write exactly once, no forged one
    of_tx = {lg["tx"][i].upper(): i for i in burst}
    seen: Counter = Counter()
    block_txs = []
    head = rpc.height(addrs[0])
    for h in range(max(h_open, 0) + 1, head + 1):
        txs = rpc.call(addrs[0], "block", {"height": h}, timeout=30)["block"][
            "data"]["txs"] or []
        block_txs.append(len(txs))
        for t in txs:
            i = of_tx.get(t.upper())
            if i is not None:
                seen[i] += 1
    chain_off = sum(abs(seen[i] - 1) for i in burst if i not in forged)
    forged_in_chain = sum(seen[i] for i in forged)
    obs.series["block_txs"] = [float(x) for x in block_txs]

    # (b) every forged write refused at CheckTx; the reference's verdict
    #     on them and on a sample of the valid ones
    forged_accepted = sum(1 for i in forged if lg["code"][i] == 0)
    rng = random.Random(seed ^ 0x5EED)
    valid = [i for i in burst if i not in forged]
    sample = rng.sample(valid, min(len(valid), int(mix["readback_sample"])))
    disagreements = sum(1 for i in sorted(forged) + sample
                        if ref.verdict(bytes.fromhex(lg["tx"][i])) == (i in forged))
    refused = sum(1 for i in valid if lg["code"][i] not in (0, None))

    # (c) each committed write's result on the node it was sent to: code
    #     0 at the height its event named, the tx the generator sent
    def off(i):
        res = _tx_result(addrs[lg["node"][i]], lg["hash"][i])
        return res is None or res["height"] != lg["height"][i] \
            or res["tx_result"]["code"] != 0 \
            or res["tx"].upper() != lg["tx"][i].upper()

    with ThreadPoolExecutor(16) as pool:
        results_off = sum(pool.map(off, committed))

    # (d), (e), (f): validator_net's comparisons over the committed
    #     writes (read-back from every node against kv_ref, each in the
    #     block its event named, agreement at every height, the device
    #     alone); its own forged writes are replaced by the burst's
    view = {"ok": [True] * len(committed),
            "key": [lg["key"][i] for i in committed],
            "value": [lg["value"][i] for i in committed],
            "tx": [lg["tx"][i] for i in committed],
            "height": [lg["height"][i] for i in committed]}
    ten = vn.judge(ctx, cfg, {**mix, "forged_writes": 0}, addrs, view,
                   list(range(len(committed))), top, unanswered, status0, daemon)
    ten = [c for c in ten if c[0] not in ("forged_writes_accepted",
                                          "reference_verdict_disagreements")]
    comparisons = ten + [
        ("writes_unlike_reference", off_plan, 0),
        ("valid_burst_writes_not_once_in_chain", chain_off, 0),
        ("forged_writes_in_chain", forged_in_chain, 0),
        ("forged_writes_accepted", forged_accepted, 0),
        ("reference_verdict_disagreements", disagreements, 0),
        ("valid_writes_refused", refused, 0),
        ("tx_results_unlike_event", results_off, 0),
        ("forged_writes_not_sent", int(mix["forged_writes"]) - len(forged), 0),
    ]
    return comparisons, {"block_txs": block_txs,
                         "forged_writes_accepted_n": forged_accepted}
