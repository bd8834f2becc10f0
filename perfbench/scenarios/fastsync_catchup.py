"""Deployment `fastsync_catchup`: a chain signed by N validator keys sits
in the block stores of P serving node processes; one fresh node of the
normal CLI, fast sync on, dials all of them and catches up through
BlockchainReactor -> verify_commits_async -> gateway -> devd -> the comb
kernel. Traffic `catchup`: the window measures steady catch-up.

The timed path is the p2p fast-sync path into the node's block store and
app. What it produced is judged after the window against the served
chain as made (`harness/chain.ChainRecord`) and the plain reference
(`reference/kv_ref.py`, `reference/ed25519_ref.py`): see `judge`.
"""

from __future__ import annotations

import math
import os
import random
import subprocess
import threading
import time

from harness import chain, device, procs, rpc
from harness.chain import derive
from harness.observe import Observations, sleep_until
from reference import ed25519_ref, kv_ref

GAUGES = ("fastsync_blocks_synced", "fastsync_dispatch_s", "fastsync_part_hash_s",
          "fastsync_verify_wait_s", "fastsync_store_save_s", "fastsync_apply_s",
          "gateway_verify_tpu_sigs", "gateway_verify_cpu_sigs")


def _bystander(seed: int, label: str):
    """A node key that is not in the validator set."""
    from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
    from tendermint_tpu.types import PrivValidatorFS

    return PrivValidatorFS(gen_priv_key_ed25519(derive(seed, "node", label)), None)


def _snapshot(addr, daemon) -> dict:
    m = rpc.metrics(addr)
    t = time.time()
    recv = rpc.prom_sum(addr, "p2p_peer_recv_bytes_total")
    height = rpc.height(addr)
    st = daemon.status()["stats"]
    return {"t": t, "gauges": {k: float(m.get(k, 0)) for k in GAUGES},
            "active": int(m.get("fastsync_active", 0)), "recv_bytes": recv,
            "height": height, "breaker": int(m.get("gateway_verify_breaker_state", 0)),
            "daemon": {"tpu_sigs": st["tpu_sigs"], "cpu_sigs": st["cpu_sigs"]}}


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    t_setup = time.time()
    run_dir = ctx.run_dir
    native_s = procs.build_native()
    daemon = procs.Daemon(run_dir, cfg["daemon"], control=ctx.control,
                          accept_cpu=ctx.rehearsal)

    n_val = int(cfg["validators"])
    n_peers = int(mix["peers"])
    lead_blocks = int(mix["lead_in_blocks"])
    n_blocks = lead_blocks + math.ceil(
        float(cfg["chain"]["headroom"]) * float(mix["expected_blocks_per_s"])
        * (ctx.seconds + float(mix["lead_in_s_for_sizing"])))
    chain_id = f"perfbench-{cfg['name']}"
    pay = cfg["payload"]
    # the chain (half a minute of signing and storing) is built, installed
    # and served by a thread of its own, beside the daemon's claim and the
    # warm-up, which need the validator keys only
    ports = procs.free_ports(2 * (n_peers + 1))
    common = {"chain_id": chain_id, "proxy_app": cfg["app"]}
    servers: list = []
    made: dict = {}

    def build_and_serve():
        try:
            rec = chain.build_chain(
                os.path.join(run_dir, "chain"), seed=ctx.seed, chain_id=chain_id,
                n_validators=n_val, n_blocks=n_blocks,
                txs_per_block=int(pay["txs_per_block"]),
                value_bytes=int(pay["value_bytes"]),
                n_tx_signers=int(pay["tx_signers"]),
                n_workers=max(1, min(8, (os.cpu_count() or 2) - 3)))
            from tendermint_tpu.types import GenesisDoc

            genesis = GenesisDoc.from_file(rec.genesis_path)
            for i in range(n_peers):
                home = os.path.join(run_dir, f"server{i}")
                procs.write_home(home, genesis, _bystander(ctx.seed, f"server{i}"), {
                    "base": {**common, "moniker": f"server{i}", "fast_sync": False,
                             **cfg.get("base", {})},
                    "p2p": dict(cfg["p2p"])})
                chain.install_copy(rec, home)
                nd = procs.Node(home, i, ports[2 * i], ports[2 * i + 1])
                # a serving node verifies nothing: it stays off the daemon
                nd.start([], {"TENDERMINT_TPU_DISABLE": "1"})
                servers.append(nd)
            made["rec"], made["genesis"] = rec, genesis
        except BaseException as exc:  # noqa: BLE001 — re-raised by the run
            made["error"] = exc

    builder = threading.Thread(target=build_and_serve, name="chain-builder")
    builder.start()

    held = daemon.wait_held(time.time() + 900)
    dev = device.check_device(daemon, held, int(ctx.workload["chips"]),
                              ctx.rehearsal)
    dcfg = cfg["daemon"]
    chunk = int(dcfg["env"].get("TENDERMINT_DEVD_CHUNK") or 0) or None
    warm_status0 = daemon.status()["stats"]["tpu_sigs"]
    warm = device.warm_tables(daemon, chain.validator_lanes(ctx.seed, n_val),
                              max(dcfg["warm_buckets"]),
                              int(dcfg.get("warm_passes", 2)), chunk)
    warm_lanes = daemon.status()["stats"]["tpu_sigs"] - warm_status0
    builder.join(timeout=600)
    if builder.is_alive() or "error" in made:
        raise procs.HarnessError(f"the chain was not built: {made.get('error')}")
    rec, genesis = made["rec"], made["genesis"]

    def alive():
        for nd in servers:
            nd.check_alive()

    if not rpc.wait_heights([s.rpc_addr for s in servers], n_blocks,
                            time.time() + 300, alive):
        raise procs.HarnessError("the serving nodes did not load the chain: "
                                 + procs.tail(servers[0].log))
    home = os.path.join(run_dir, "syncer")
    procs.write_home(home, genesis, _bystander(ctx.seed, "syncer"), {
        "base": {**common, "moniker": "syncer", "fast_sync": True,
                 **cfg.get("syncer_base", {})},
        "p2p": dict(cfg["p2p"])})
    syncer = procs.Node(home, 99, ports[-2], ports[-1])
    syncer.start([f"127.0.0.1:{s.p2p_port}" for s in servers],
                 {**cfg["node_env"], "TENDERMINT_DEVD_SOCK": daemon.sock},
                 fast_sync=True)
    addr = syncer.rpc_addr
    deadline = time.time() + 300
    while True:
        syncer.check_alive()
        alive()
        try:
            if rpc.metrics(addr).get("fastsync_blocks_synced", 0) >= lead_blocks:
                break
        except (OSError, rpc.RPCFailure):
            pass
        if time.time() > deadline:
            raise procs.HarnessError("the syncing node applied no blocks: "
                                     + procs.tail(syncer.log))
        time.sleep(0.1)

    # -- the window ------------------------------------------------------
    snap0 = _snapshot(addr, daemon)
    open_wall = snap0["t"]
    close_wall = open_wall + ctx.seconds
    setup_s = open_wall - t_setup
    obs = Observations(window_s=ctx.seconds, open_wall=open_wall)
    trace = None
    if ctx.trace:
        trace = ctx.start_trace(daemon, close_wall, float(mix["trace_window_s"]))
        trace["message_bytes"] = len(rec.sign_bytes[0])
        trace["distinct_keys"] = n_val
    sleep_until(close_wall)
    snap1 = _snapshot(addr, daemon)
    if trace:
        ctx.finish_trace(daemon, trace)
    launcher = daemon.request("snapshot", since_ns=int(open_wall * 1e9))
    syncer.check_alive()
    alive()
    window_s = snap1["t"] - snap0["t"]
    obs.window_s = window_s
    if not snap1["active"] or snap1["height"] >= n_blocks - 2:
        raise procs.HarnessError(
            f"the chain of {n_blocks} blocks did not outlast the window "
            f"(height {snap1['height']})")
    d_synced = snap1["gauges"]["fastsync_blocks_synced"] - snap0["gauges"]["fastsync_blocks_synced"]
    d_height = snap1["height"] - snap0["height"]
    for k in GAUGES:
        obs.counters["sync." + k] = (snap0["gauges"][k], snap1["gauges"][k])
    obs.counters["sync.p2p_recv_bytes"] = (snap0["recv_bytes"], snap1["recv_bytes"])
    for k in ("tpu_sigs", "cpu_sigs"):
        obs.counters["daemon." + k] = (snap0["daemon"][k], snap1["daemon"][k])
    obs.scalars["serving_peers"] = float(n_peers)
    obs.scalars["recv_rate_bytes_per_s"] = float(cfg["p2p"]["recv_rate"])
    obs.set_launcher(launcher, open_wall, snap1["t"])
    obs.trace = trace

    # -- after the window --------------------------------------------------
    dev_after = daemon.request("device")
    comparisons = judge(ctx, cfg, mix, rec, addr, servers[0].rpc_addr, snap0,
                        snap1, warm_lanes, d_synced, d_height, daemon, chunk,
                        [syncer] + servers)
    daemon_code = daemon.shutdown()
    e2e = {"setup_s": setup_s}
    if d_synced > 0:
        e2e["catchup_blocks_per_s"] = d_synced / window_s
    return {
        "attempted": int(d_synced), "failed": 0,
        "end_to_end": e2e, "obs": obs, "comparisons": comparisons,
        "device": {**dev, "memory_peak_bytes": dev_after["memory_peak_bytes"]},
        "notes": {"native_build_s": round(native_s, 2), "warm": warm,
                  "chain_blocks": n_blocks, "chain_build_s": round(rec.build_s, 2),
                  "block_wire_bytes": rec.block_bytes, "peers": n_peers,
                  "height_open": snap0["height"], "height_close": snap1["height"],
                  "recv_bytes_per_s": (snap1["recv_bytes"] - snap0["recv_bytes"]) / window_s,
                  "daemon_lanes_in_window": snap1["daemon"]["tpu_sigs"] - snap0["daemon"]["tpu_sigs"],
                  "compiles_in_window": len(obs.compiles_in_window),
                  "batch_lanes_in_window": obs.lanes_histogram(),
                  "trace": {k: v for k, v in (trace or {}).items()
                            if k not in ("extracted", "dir")},
                  "daemon_exit_code": daemon_code, "setup_s": round(setup_s, 3),
                  "claim_s": held.get("claim", {}).get("claim_s")},
    }


def judge(ctx, cfg, mix, rec, addr, server_addr, snap0, snap1, warm_lanes,
          d_synced, d_height, daemon, chunk, nodes):
    """Every number compared, beside its limit. All comparisons are
    exact, so every limit is 0."""
    rng = random.Random(ctx.seed ^ 0xC0FFEE)
    h_end = snap1["height"]
    # 1. identity: the node holds the served chain's block hash at every
    #    height it applied, and headers carry the served app hashes; one
    #    serving node is read the same way (equal across nodes)
    hash_bad, app_bad, cross_bad = 0, 0, 0
    for lo in range(1, h_end + 1, 20):
        hi = min(h_end, lo + 19)
        mine = {m["header"]["height"]: m for m in rpc.call(
            addr, "blockchain", {"min_height": lo, "max_height": hi})["block_metas"]}
        theirs = {m["header"]["height"]: m for m in rpc.call(
            server_addr, "blockchain", {"min_height": lo, "max_height": hi})["block_metas"]}
        for h in range(lo, hi + 1):
            m = mine.get(h)
            if m is None or m["block_id"]["hash"].upper() != rec.block_hash[h - 1]:
                hash_bad += 1
                continue
            want_app = rec.app_hash_after[h - 2] if h >= 2 else ""
            if (m["header"]["app_hash"] or "").upper() != want_app:
                app_bad += 1
            t = theirs.get(h)
            if t is None or t["block_id"] != m["block_id"]:
                cross_bad += 1
    # 2. state: a seed-drawn sample of the keys the chain wrote up to the
    #    window's last height (the last block's with it) answers with the
    #    value the plain reference holds; the sampled txs' signatures are
    #    valid by the plain reference
    ref = kv_ref.KVReference()
    written = []
    for h in range(1, h_end + 1):
        for tx in rec.txs[h - 1]:
            payload = kv_ref.split_tx(tx)[1]
            ref.apply_payload(payload)
            written.append((payload.split(b"=", 1)[0], tx))
    tail_n = len(rec.txs[h_end - 1])
    k = min(len(written) - tail_n, int(mix["state_sample_keys"]))
    sample = rng.sample(written[:-tail_n], k) + written[-tail_n:]
    state_bad, ref_disagrees = 0, 0
    for j, (key, tx) in enumerate(sample):
        res = rpc.call(addr, "abci_query", {"data": key.hex()})["response"]
        if bytes.fromhex(res.get("value") or "") != ref.get(key):
            state_bad += 1
        if j % 8 == 0 and not kv_ref.tx_valid(tx):
            ref_disagrees += 1
    # the nodes have said all they will: stop them before the probe
    for nd in nodes:
        nd.proc.terminate()
    for nd in nodes:
        try:
            nd.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            nd.proc.kill()
    # 3. verdicts: commits of the window go through the daemon once more,
    #    framed as the node frames them, with lanes altered at seed-drawn
    #    places; every altered lane and a sample of the others is compared
    #    with plain Ed25519, lane for lane
    verdict_bad = 0
    lo_h = max(1, snap0["height"])
    for c in range(int(mix["probe_commits"])):
        h = rng.randint(lo_h, h_end)
        msg = rec.sign_bytes[h - 1]
        lanes = [(rec.pubkeys[i], msg, rec.signatures[h - 1][i])
                 for i in range(rec.n_validators)]
        altered = rng.sample(range(rec.n_validators), int(mix["probe_corrupt_lanes"]))
        for j, i in enumerate(altered):
            pk, m, sig = lanes[i]
            if j % 2 == 0:
                sig = sig[:9] + bytes([sig[9] ^ 0x10]) + sig[10:]
            else:
                m = m + b" "
            lanes[i] = (pk, m, sig)
        got = device.send(daemon, lanes, chunk)
        others = rng.sample([i for i in range(rec.n_validators) if i not in altered],
                            min(40, rec.n_validators - len(altered)))
        for i in list(altered) + others:
            if bool(got[i]) != ed25519_ref.verify(*lanes[i]):
                verdict_bad += 1
    # 4. nothing skipped: verification comes before application, so at the
    #    window's close the device has verified at least 1000 lanes for
    #    every block applied (the warm-up's lanes taken off)
    st = daemon.status()["stats"]
    verified = snap1["daemon"]["tpu_sigs"] - warm_lanes
    applied = snap1["gauges"]["fastsync_blocks_synced"]
    short = max(0.0, rec.n_validators * applied - verified)
    return [
        ("block_hash_mismatches", hash_bad, 0),
        ("app_hash_mismatches", app_bad, 0),
        ("blocks_differing_from_serving_node", cross_bad, 0),
        ("state_readback_mismatches", state_bad, 0),
        ("reference_rejects_a_served_tx", ref_disagrees, 0),
        ("verdict_mismatches_vs_plain_ed25519", verdict_bad, 0),
        ("lanes_verified_short_of_blocks_applied", short, 0),
        ("synced_count_vs_store_height_gap", max(0.0, abs(d_synced - d_height) - 1), 0),
        ("daemon_cpu_sigs", st["cpu_sigs"], 0),
        ("sync_node_breaker_not_closed", 1 if snap1["breaker"] else 0, 0),
        ("window_applied_no_block", 0 if d_synced > 0 else 1, 0),
    ]
