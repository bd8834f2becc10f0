"""Deployment `validator_net`: N validator processes of the normal CLI
node on loopback, one app, the daemon on the chip; traffic
`open_loop_writes` through the public RPC.

The timed path is `broadcast_tx_commit` on the four RPC ports. What it
produced is judged after the window against the plain reference
(`reference/kv_ref.py`, which verifies with `reference/ed25519_ref.py`):
see `judge`.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time

from harness import device, procs, rpc
from harness.chain import derive
from harness.observe import Observations, quantile, sleep_until
from reference import kv_ref


def _validators(seed: int, n: int):
    from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
    from tendermint_tpu.types import PrivValidatorFS

    pvs = [PrivValidatorFS(gen_priv_key_ed25519(derive(seed, "val", i)), None)
           for i in range(n)]
    # node i holds the i-th validator BY ADDRESS: the proposer rotates in
    # address order, and which node dialled which is fixed by index, so
    # every seed gets the same rotation over the same topology
    return sorted(pvs, key=lambda pv: pv.get_address())


def _warm_items(seed: int, pvs, n_signers: int) -> list:
    """One valid lane per key the window will show the daemon: the
    write signers and the validators."""
    from tendermint_tpu.crypto import ed25519 as ed

    items = []
    for k in range(n_signers):
        secret = derive(seed, "signer", k)
        msg = b"warm-%d" % k
        items.append((ed.public_key(secret), msg, ed.sign(secret, msg)))
    for i, pv in enumerate(pvs):
        msg = b"warm-val-%d" % i
        items.append((pv.get_pub_key().raw, msg, pv.priv_key.sign(msg).raw))
    return items


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    t_setup = time.time()
    run_dir = ctx.run_dir
    native_s = procs.build_native()
    daemon = procs.Daemon(run_dir, cfg["daemon"], control=ctx.control,
                          accept_cpu=ctx.rehearsal)

    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    n = int(cfg["validators"])
    chain_id = f"perfbench-{cfg['name']}"
    pvs = _validators(ctx.seed, n)
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
                "consensus": dict(cfg["consensus"])}
        procs.write_home(home, genesis, pv, sets)
        nodes.append(procs.Node(home, i, ports[2 * i], ports[2 * i + 1]))
    addrs = [nd.rpc_addr for nd in nodes]

    # the generator, a process of its own, prepares its writes meanwhile
    gen_files = {k: os.path.join(run_dir, f"loadgen.{k}")
                 for k in ("params", "ready", "start", "window", "out", "log")}
    with open(gen_files["params"], "w") as f:
        json.dump({
            "seed": ctx.seed, "seconds": ctx.seconds,
            "rate_per_s": mix["rate_per_s"], "arrivals": mix["arrivals"],
            "lead_in_s": mix["lead_in_s"], "signers": mix["signers"],
            "request_timeout_s": mix["request_timeout_s"],
            "targets": [list(a) for a in addrs],
            "bench_dir": procs.BENCH,
            "ready_file": gen_files["ready"], "start_file": gen_files["start"],
            "window_file": gen_files["window"], "out_file": gen_files["out"],
        }, f)
    gen = subprocess.Popen(
        [sys.executable, os.path.join(procs.HERE, "loadgen.py"),
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
    # every key's table BEFORE the nodes start: a key that a node showed
    # the daemon first would build its table alone, a program (one per
    # count of new keys) this warm-up does not make. The bucket widths
    # warm while the nodes boot.
    dcfg = cfg["daemon"]
    items = _warm_items(ctx.seed, pvs, int(mix["signers"]))
    warm = device.warm_tables(daemon, items, max(dcfg["warm_buckets"]),
                              int(dcfg.get("warm_passes", 2)))
    node_env = {**cfg["node_env"], "TENDERMINT_DEVD_SOCK": daemon.sock}
    for nd in nodes:
        nd.start([f"127.0.0.1:{m.p2p_port}" for m in nodes[:nd.index]], node_env)
    warm.update(device.warm_buckets(daemon, items, dcfg["warm_buckets"][:-1]))
    warm["total"] = round(sum(warm.values()), 3)
    marks["warmed"] = time.time() - t_setup

    def alive():
        for nd in nodes:
            nd.check_alive()

    if not rpc.wait_heights(addrs, 2, time.time() + 300, alive):
        raise procs.HarnessError("the nodes did not reach height 2: "
                                 + procs.tail(nodes[0].log))
    marks["height_2"] = time.time() - t_setup
    _wait_file(gen_files["ready"], 120, gen)
    status0 = daemon.status()
    open(gen_files["start"], "w").close()
    _wait_file(gen_files["window"], 30, gen)
    with open(gen_files["window"]) as f:
        win = json.load(f)
    open_wall, close_wall = win["open_wall"], win["close_wall"]
    setup_s = open_wall - t_setup

    # -- the window ------------------------------------------------------
    obs = Observations(window_s=ctx.seconds, open_wall=open_wall)
    sleep_until(open_wall)
    snap0 = _snapshot(addrs, daemon)
    trace = None
    if ctx.trace:
        trace = ctx.start_trace(daemon, close_wall, float(mix["trace_window_s"]))
        # what the trace's readers need of the batches' shapes: the widths
        # the daemon pads to and the keys resident (messages are 20-150
        # bytes beside 12 KB of table entries a lane, and are left out)
        trace["widths"] = list(dcfg["warm_buckets"])
        trace["distinct_keys"] = len(items)
    sleep_until(close_wall)
    snap1 = _snapshot(addrs, daemon)
    if trace:
        ctx.finish_trace(daemon, trace)
    launcher = daemon.request("snapshot", since_ns=int(open_wall * 1e9))
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
    k0 = lg["lead_in_writes"]
    idx = list(range(k0, len(lg["due"])))
    lat = [1000.0 * (lg["done"][i] - lg["due"][i]) for i in idx if lg["ok"][i]]
    failed = sum(1 for i in idx if not lg["ok"][i])
    unanswered = sum(1 for i in idx
                     if lg["done"][i] is None or "Timeout" in (lg["err"][i] or ""))
    obs.series["commit_latency_ms"] = lat
    obs.series["generator_late_ms"] = [
        1000.0 * (lg["sent"][i] - lg["due"][i]) for i in idx]
    top = max([lg["height"][i] for i in idx if lg["ok"][i]] or [0])
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
    obs.set_launcher(launcher, open_wall, close_wall)
    obs.trace = trace

    comparisons = judge(ctx, cfg, mix, addrs, lg, idx, top, unanswered,
                        status0, daemon)
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
    return {
        "attempted": len(idx), "failed": failed,
        "end_to_end": metrics_e2e, "obs": obs, "comparisons": comparisons,
        "device": {**dev, "memory_peak_bytes": dev_after["memory_peak_bytes"]},
        "notes": {"native_build_s": round(native_s, 2), "warm": warm,
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


def judge(ctx, cfg, mix, addrs, lg, idx, top, unanswered, status0, daemon):
    """Every number compared, beside its limit. All comparisons are
    exact, so every limit is 0."""
    rng = random.Random(ctx.seed ^ 0x5EED)
    acked = [i for i in idx if lg["ok"][i]]
    ref = kv_ref.KVReference()
    for i in acked:
        ref.apply_payload(bytes.fromhex(lg["key"][i]) + b"="
                          + bytes.fromhex(lg["value"][i]))
    # 1. read back a seed-drawn sample of the acknowledged writes, the
    #    last one with it, from ALL nodes, against the plain reference
    sample = sorted(set(rng.sample(acked, min(len(acked), int(mix["readback_sample"])))
                        + acked[-1:]))
    mismatches = 0
    for i in sample:
        key = bytes.fromhex(lg["key"][i])
        for a in addrs:
            res = rpc.call(a, "abci_query", {"data": key.hex()})["response"]
            if bytes.fromhex(res.get("value") or "") != ref.get(key):
                mismatches += 1
    # 2. every acknowledged write is in the block at the height it names,
    #    exactly once, on node 0
    by_height: dict[int, list[int]] = {}
    for i in acked:
        by_height.setdefault(lg["height"][i], []).append(i)
    not_in_block = 0
    for h, members in sorted(by_height.items()):
        blk = rpc.call(addrs[0], "block", {"height": h})["block"]
        txs = [t.upper() for t in (blk["data"]["txs"] or [])]
        for i in members:
            if txs.count(lg["tx"][i].upper()) != 1:
                not_in_block += 1
    # 3. agreement: block hash, parts root and app hash equal on all
    #    nodes at every height up to the last acknowledged one
    diverging = 0
    prints = []
    for a in addrs:
        per = {}
        for lo in range(1, top + 1, 20):
            metas = rpc.call(a, "blockchain", {"min_height": lo,
                                               "max_height": min(top, lo + 19)})
            for m in metas["block_metas"]:
                per[m["header"]["height"]] = (
                    m["block_id"]["hash"], m["block_id"]["parts"]["hash"],
                    m["header"]["app_hash"])
        prints.append(per)
    for h in range(1, top + 1):
        if len({p.get(h) for p in prints}) != 1 or prints[0].get(h) is None:
            diverging += 1
    # 4. the signature gate: forged writes (a signature bit or a payload
    #    byte altered) must be refused at CheckTx and change nothing; the
    #    reference gives the verdict each deserves
    forged_accepted = 0
    ref_disagrees = 0
    n_forged = int(mix["forged_writes"])
    for k in range(n_forged):
        i = acked[(k * 7919) % len(acked)] if acked else None
        if i is None:
            break
        tx = bytearray(bytes.fromhex(lg["tx"][i]))
        fkey = b"forged%d-%d" % (ctx.seed % 1000003, k)
        if k % 2 == 0:
            tx[32 + 5] ^= 0x40                       # the signature
        else:
            tx = tx[:kv_ref.SIG_TX_OVERHEAD] + fkey + b"=x"   # the message
        tx = bytes(tx)
        if kv_ref.tx_valid(tx):
            ref_disagrees += 1
        res = rpc.call(addrs[k % len(addrs)], "broadcast_tx_commit",
                       {"tx": tx.hex()}, timeout=30)
        if (res.get("check_tx") or {}).get("code", 0) == 0:
            forged_accepted += 1
        if not kv_ref.tx_valid(bytes.fromhex(lg["tx"][i])):
            ref_disagrees += 1
    # 5. the daemon answered from the device alone, and the nodes' breakers
    #    stayed closed
    status1 = daemon.status()
    d_cpu = status1["stats"]["cpu_sigs"] - 0
    d_tpu = status1["stats"]["tpu_sigs"] - status0["stats"]["tpu_sigs"]
    breakers = 0
    for a in addrs:
        m = rpc.metrics(a)
        if m.get("gateway_verify_breaker_state", 0) != 0:
            breakers += 1
    return [
        ("writes_never_answered", unanswered, 0),
        ("readback_mismatches", mismatches, 0),
        ("acked_writes_not_in_their_block", not_in_block, 0),
        ("heights_diverging_across_nodes", diverging, 0),
        ("forged_writes_accepted", forged_accepted, 0),
        ("reference_verdict_disagreements", ref_disagrees, 0),
        ("daemon_cpu_sigs", d_cpu, 0),
        ("window_without_device_lanes", 0 if d_tpu > 0 else 1, 0),
        ("node_breakers_not_closed", breakers, 0),
        ("writes_acknowledged_is_zero", 0 if acked else 1, 0),
    ]


SUMMED = ("consensus_vote_batches", "consensus_vote_batched_sigs",
          "consensus_vote_singletons", "gateway_verify_tpu_sigs",
          "gateway_verify_cpu_sigs", "gateway_verify_tpu_batches")


def _snapshot(addrs, daemon) -> dict:
    total = {k: 0.0 for k in SUMMED}
    for a in addrs:
        m = rpc.metrics(a)
        for k in SUMMED:
            total[k] += float(m.get(k, 0))
    st = daemon.status()["stats"]
    return {"sum": total, "daemon": {"tpu_sigs": st["tpu_sigs"],
                                     "cpu_sigs": st["cpu_sigs"]}}


def _wait_file(path: str, timeout: float, proc) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            return
        if proc.poll() is not None:
            raise procs.HarnessError(
                f"the generator exited with {proc.returncode} before {path}")
        time.sleep(0.02)
    raise procs.HarnessError(f"{path} did not appear in {timeout}s")
