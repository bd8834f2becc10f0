#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one chip: build, claim, commit-1000,
                                     # net-4, release
    python3 chip_smoke.py --chips 4  # four daemons, one per chip, behind
                                     # one gateway — and nothing else

This process is a LAUNCHER and a CLIENT: it never imports JAX. libtpu gives
a chip to one process, and that process is the device daemon
(`python -m tendermint_tpu.devd`) this script starts as a child. Validator
nodes are children too, pinned to JAX_PLATFORMS=cpu: they reach the chip
only through the daemon's socket, as a deployment does.

Each phase prints one JSON line when it ends. A phase that fails ends the
run at once: the last line is then {"ok": false, ...} and the exit code is
not 0. On success the last line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the daemon (the one process that may ask JAX) reports it.

A developer's rehearsal without a chip: TENDERMINT_DEVD_ACCEPT_CPU=1
JAX_PLATFORMS=cpu python3 chip_smoke.py --validators 40 --txs 8 (a
committee under 32 stays below the gateway's default device floor). It runs
every phase against a CPU daemon and still ends {"ok": false}: the platform
is not tpu.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
CHAIN_ID = "chipsmoke"

# bounds, in seconds; the whole run must fit 1200 with compilation
WATCHDOG_S = 1150
BUILD_S = 180
CLAIM_S = 840
NET_BOOT_S = 420

# every child this script starts, so that nothing outlives it
_children: list[subprocess.Popen] = []


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def no_jax_here() -> None:
    check("jax" not in sys.modules, "the launcher imported jax")


# -- build --------------------------------------------------------------------


def phase_build() -> None:
    """Rebuild the native host library from the committed sources, on
    this machine: the host verifier is what the device's verdicts are
    compared with, and a library built with -march=native elsewhere is
    not this machine's."""
    t0 = time.time()
    native_dir = os.path.join(ROOT, "native")
    for cmd in (["make", "-C", native_dir, "clean"], ["make", "-C", native_dir]):
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=BUILD_S)
        check(r.returncode == 0, f"{' '.join(cmd)} failed: {r.stderr[-1500:]}")
    from tendermint_tpu import native

    check(native.available(), "the rebuilt native library does not load")
    emit({"phase": "build", "ok": True, "seconds": round(time.time() - t0, 2),
          "library": os.path.relpath(native._LIB_PATH, ROOT)})


# -- daemons ------------------------------------------------------------------


def sock_dir() -> str:
    """Unix socket paths are capped near 107 bytes; a deep checkout falls
    back to the temporary directory the caller gave this process."""
    if len(os.path.join(OUT, "devd-0.sock")) < 100:
        return OUT
    return tempfile.mkdtemp(prefix="chipsmoke-")


def daemon_env(sock: str, chip: int | None) -> dict:
    """Production settings for a daemon child: no simulated device, no
    platform told from outside; SIGTERM honoured so nothing outlives
    the run. ACCEPT_CPU passes through only because the developer's
    rehearsal sets it — the run then ends ok:false."""
    env = dict(os.environ)
    for k in ("TENDERMINT_DEVD_SIM_RATE", "TENDERMINT_TPU_DISABLE",
              "TENDERMINT_TPU_PLATFORM", "TENDERMINT_TPU_KERNEL",
              "TENDERMINT_DEVD_SOCKS"):
        env.pop(k, None)
    env["TENDERMINT_DEVD_SOCK"] = sock
    env["TENDERMINT_DEVD_EXIT_ON_TERM"] = "1"
    # a cold f32p compile is ~2 min PER warm shape: the default warm set
    # (1024,4096,8192 x comb+f32p) does not fit this script's limit, so
    # the claim warms the one shape the phases below send
    env.setdefault("TENDERMINT_DEVD_WARM", "1024")
    env["PYTHONPATH"] = ROOT
    if chip is not None:
        # one daemon per chip: the libtpu environment that shows this
        # child one chip only. The launcher sets it; the program grows
        # no option for it.
        # comb pinned: the bake-off is the one-chip run's business, an
        # f32p compile per daemon would quadruple the claim's cost, and
        # comb is the kernel whose per-validator pool this batch's 1000
        # keys fill (where f32p wins the one-chip bake-off, the one-chip
        # commit never reaches it)
        env.setdefault("TENDERMINT_DEVD_KERNEL", "comb")
        env.update({
            "TPU_VISIBLE_CHIPS": str(chip),
            "TPU_VISIBLE_DEVICES": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{8476 + chip}",
            "TPU_MESH_CONTROLLER_PORT": str(8476 + chip),
        })
    return env


def start_daemon(sock: str, log_path: str, chip: int | None = None):
    log = open(log_path, "ab")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.devd"],
        env=daemon_env(sock, chip), cwd=ROOT, stdout=log, stderr=log,
        start_new_session=True,
    )
    _children.append(proc)
    return proc


def wait_held(procs, socks, logs, deadline: float) -> list[dict]:
    """Wait, bounded, until every daemon holds its device; a daemon that
    exits or reports a failed claim ends the wait at once."""
    from tendermint_tpu import devd

    status: list[dict | None] = [None] * len(procs)
    while time.time() < deadline:
        for i, (proc, sock) in enumerate(zip(procs, socks)):
            if status[i] is not None:
                continue
            rep = None
            if os.path.exists(sock):
                try:
                    # generous: tracing the unrolled Pallas ladder holds
                    # the daemon's GIL for seconds at a time
                    c = devd.DevdClient(sock, connect_timeout=2.0, io_timeout=30.0)
                    rep = c.status(timeout=30.0)
                    c.close()
                except Exception:  # noqa: BLE001 — not listening yet
                    rep = None
            if rep is not None and rep.get("status") == "failed":
                raise SmokeFailure(
                    f"daemon {i} reports a failed claim: {rep.get('error')}\n"
                    + tail(logs[i])
                )
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"daemon {i} exited with code {proc.returncode} before "
                    f"it held a device\n" + tail(logs[i])
                )
            if rep is not None and rep.get("held"):
                status[i] = rep
        if all(s is not None for s in status):
            return status  # type: ignore[return-value]
        time.sleep(1.0)
    raise SmokeFailure(
        "daemon(s) did not hold a device within the bound: "
        + "; ".join(tail(p, 600) for p in logs)
    )


def claim_line(rep: dict, seconds: float) -> dict:
    claim = rep.get("claim", {})
    return {
        "platform": rep.get("platform"),
        "device_kind": rep.get("device_kind"),
        "device_count": rep.get("device_count"),
        "device_ids": rep.get("device_ids"),
        "visible_chips": rep.get("visible_chips"),
        "cache_dir": claim.get("cache_dir"),
        "claim_seconds": claim.get("claim_s"),
        "wait_seconds": round(seconds, 2),
        "kernels": claim.get("kernels"),
        "chunk_rates": claim.get("chunk_rates"),
        "served": claim.get("served"),
        "warmed": rep.get("warmed"),
        "stats": rep.get("stats"),
    }


def check_claim(rep: dict, deferred: list[str], rehearsal: bool) -> None:
    check(rep.get("error") is None, f"daemon reports an error: {rep.get('error')}")
    stats = rep.get("stats", {})
    check(stats.get("cpu_sigs", 1) == 0,
          f"the daemon's verifier answered {stats.get('cpu_sigs')} warm lanes "
          f"from the host")
    check(stats.get("tpu_sigs", 0) > 0, "the warm-up sent the device nothing")
    if rep.get("platform") != "tpu":
        msg = f"the daemon's platform is {rep.get('platform')!r}, not 'tpu'"
        check(rehearsal, msg)
        deferred.append(msg)
        return
    for kname, krep in (rep.get("claim", {}).get("kernels") or {}).items():
        if "interpret" in krep:
            check(krep["interpret"] == [False],
                  f"Pallas kernel {kname} built with interpret={krep['interpret']}")


def phase_claim(deferred: list[str], rehearsal: bool):
    t0 = time.time()
    sock = os.path.join(sock_dir(), "devd-0.sock")
    log = os.path.join(OUT, "devd-0.log")
    proc = start_daemon(sock, log)
    rep = wait_held([proc], [sock], [log], t0 + CLAIM_S)[0]
    check_claim(rep, deferred, rehearsal)
    emit({"phase": "claim", "ok": True, **claim_line(rep, time.time() - t0)})
    return proc, sock, rep


def daemon_stats(sock: str) -> dict:
    from tendermint_tpu import devd

    c = devd.DevdClient(sock)
    try:
        return c.status()
    finally:
        c.close()


def route_client_through(socks: list[str]) -> None:
    """Point THIS process's gateway at the daemon(s), as a node's
    environment does — and take away whatever would tell it otherwise."""
    for k in ("TENDERMINT_TPU_DISABLE", "TENDERMINT_TPU_PLATFORM",
              "TENDERMINT_TPU_KERNEL", "TENDERMINT_TPU_MIN_BATCH",
              "TENDERMINT_DEVD_SOCKS", "TENDERMINT_DEVD_SOCK"):
        os.environ.pop(k, None)
    if len(socks) == 1:
        os.environ["TENDERMINT_DEVD_SOCK"] = socks[0]
    else:
        os.environ["TENDERMINT_DEVD_SOCKS"] = ",".join(socks)
    from tendermint_tpu import devd

    devd.bust_avail_cache()


# -- commit-1000 --------------------------------------------------------------


def make_committee(n: int, seed: int):
    from tendermint_tpu.crypto.keys import gen_priv_key_ed25519
    from tendermint_tpu.types import PrivValidatorFS, Validator, ValidatorSet

    privs = [
        PrivValidatorFS(
            gen_priv_key_ed25519(b"chipsmoke-%d-val-%d" % (seed, i)), None
        )
        for i in range(n)
    ]
    vs = ValidatorSet([Validator.new(p.get_pub_key(), 10) for p in privs])
    check(vs.size() == n, f"{n} seeds gave {vs.size()} distinct validators")
    return vs, privs


def make_commit(vs, privs, height: int, block_id):
    """The precommits of every validator over the real canonical
    sign-bytes (PrivValidator.sign_vote), index-aligned with the set."""
    from tendermint_tpu.types import Commit, Vote
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT

    precommits = [None] * vs.size()
    for p in privs:
        idx, _ = vs.get_by_address(p.get_address())
        vote = Vote(
            validator_address=p.get_address(), validator_index=idx,
            height=height, round_=0, type_=VOTE_TYPE_PRECOMMIT,
            block_id=block_id,
        )
        precommits[idx] = p.sign_vote(CHAIN_ID, vote)
    return Commit(block_id, precommits)


def commit_items(vs, commit) -> list:
    """(pubkey, sign-bytes, signature) per precommit — the lanes
    verify_commit hands the batch verifier."""
    return [
        (vs.validators[i].pub_key.raw, pc.sign_bytes(CHAIN_ID), pc.signature.raw)
        for i, pc in enumerate(commit.precommits)
    ]


def refusal(vs, block_id, height, commit, batch_verifier) -> str:
    from tendermint_tpu.types.validator_set import CommitError

    try:
        vs.verify_commit(CHAIN_ID, block_id, height, commit,
                         batch_verifier=batch_verifier)
    except CommitError as exc:
        return str(exc)
    raise SmokeFailure("a corrupted commit was accepted")


def phase_commit(sock: str, n: int, seed: int) -> None:
    t0 = time.time()
    from tendermint_tpu.crypto import ed25519 as ed_host
    from tendermint_tpu.crypto.keys import SignatureEd25519
    from tendermint_tpu.ops import gateway
    from tendermint_tpu.types import BlockID, Commit, PartSetHeader, Vote

    route_client_through([sock])
    verifier = gateway.default_verifier()
    check(gateway.kernel_name() == "devd",
          f"the default verifier resolved {gateway.kernel_name()!r}, not the daemon")
    hasher_route = gateway.default_hasher().route()

    vs, privs = make_committee(n, seed)
    block_id = BlockID(b"\xaa" * 20, PartSetHeader(2, b"\xbb" * 20))
    height = 7
    commit = make_commit(vs, privs, height, block_id)
    before = daemon_stats(sock)
    sent = 0

    # three passes: first sight rides the f32 ladder, the second builds
    # the comb tables of all n keys, the third finds them resident
    pass_s, pool = [], []
    for _ in range(3):
        t = time.time()
        vs.verify_commit(CHAIN_ID, block_id, height, commit,
                         batch_verifier=verifier.commit_batch_verifier())
        pass_s.append(round(time.time() - t, 3))
        pool.append(daemon_stats(sock).get("comb_pool"))
        sent += n

    # one corrupted signature, one corrupted message: refused, with the
    # error the host path gives
    a, b = n // 3, (2 * n) // 3
    bad_sig = bytearray(commit.precommits[a].signature.raw)
    bad_sig[5] ^= 0x40
    c_sig = Commit(block_id, list(commit.precommits))
    c_sig.precommits[a] = commit.precommits[a].with_signature(
        SignatureEd25519(bytes(bad_sig))
    )
    other = BlockID(b"\xcc" * 20, PartSetHeader(2, b"\xbb" * 20))
    c_msg = Commit(block_id, list(commit.precommits))
    pc = commit.precommits[b]
    # the same vote over another block id, under the old signature
    c_msg.precommits[b] = Vote(
        validator_address=pc.validator_address, validator_index=b,
        height=height, round_=0, type_=pc.type_, block_id=other,
    ).with_signature(pc.signature)
    errors = {}
    for name, bad in (("signature", c_sig), ("message", c_msg)):
        dev = refusal(vs, block_id, height, bad,
                      verifier.commit_batch_verifier())
        host = refusal(vs, block_id, height, bad, None)
        check(dev == host, f"corrupted {name}: device path said {dev!r}, "
                           f"host path said {host!r}")
        errors[name] = dev[:80]
        sent += n

    # lane for lane against the host verifier
    items = commit_items(vs, commit)
    items[a] = (items[a][0], items[a][1], bytes(bad_sig))
    items[b] = (items[b][0], items[b][1] + b"!", items[b][2])
    got = verifier.verify_batch(items)
    want = [ed_host.verify(pk, msg, sig) for pk, msg, sig in items]
    check(got == want, "device verdicts differ from crypto.ed25519.verify at lanes "
          + str([i for i, (g, w) in enumerate(zip(got, want)) if g != w][:8]))
    check(want.count(False) == 2 and not want[a] and not want[b],
          "the host verifier did not reject exactly the two corrupted lanes")
    sent += n

    after = daemon_stats(sock)
    d_tpu = after["stats"]["tpu_sigs"] - before["stats"]["tpu_sigs"]
    d_cpu = after["stats"]["cpu_sigs"] - before["stats"]["cpu_sigs"]
    check(d_tpu >= sent, f"sent {sent} lanes, the daemon's tpu_sigs rose by {d_tpu}")
    check(d_cpu == 0, f"the daemon's cpu_sigs moved by {d_cpu}")
    cstats = verifier.stats()
    check(cstats["cpu_sigs"] == 0,
          f"the client answered {cstats['cpu_sigs']} lanes from the host")
    check(gateway.devd_breaker().state == 0, "the client's breaker is not closed")
    no_jax_here()
    emit({"phase": "commit-1000", "ok": True, "validators": n,
          "lanes_sent": sent, "daemon_tpu_sigs_delta": d_tpu,
          "daemon_cpu_sigs_delta": d_cpu, "pass_seconds": pass_s,
          "comb_pool_after_each_pass": pool, "refused_with": errors,
          "client_breaker_state": gateway.devd_breaker().state,
          "default_hasher_route": hasher_route,
          "seconds": round(time.time() - t0, 2)})


# -- net-4 --------------------------------------------------------------------


def free_base_port() -> int:
    for base in range(47100, 60000, 500):
        socks = []
        try:
            for p in range(base, base + 8):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", p))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise SmokeFailure("no free loopback port range for four nodes")


def phase_net(sock: str, n_txs: int, seed: int) -> None:
    t0 = time.time()
    from concurrent.futures import ThreadPoolExecutor

    from tendermint_tpu.abci.apps.signedkv import make_sig_tx
    from tendermint_tpu.ops.localnet import Localnet, LocalnetSpec

    before = daemon_stats(sock)
    spec = LocalnetSpec(
        n=4, root=os.path.join(OUT, "net4"), chain_id=CHAIN_ID, seed=seed,
        proxy_app="signedkv", base_port=free_base_port(), log_level="info",
        extra_env={
            # the nodes are CLIENTS of the daemon: a node that loaded
            # libtpu would fight it for the chip
            "JAX_PLATFORMS": "cpu",
            "TENDERMINT_TPU_DISABLE": "0",
            "TENDERMINT_DEVD_SOCK": sock,
            # at four validators every vote batch is narrower than the
            # default floor of 32: without this the net never touches
            # the chip
            "TENDERMINT_TPU_MIN_BATCH": "1",
        },
    )
    for k in ("TENDERMINT_TPU_PLATFORM", "TENDERMINT_TPU_KERNEL",
              "TENDERMINT_DEVD_SOCKS"):
        check(k not in os.environ, f"{k} is set in the launcher's environment")
    net = Localnet(spec).generate().start()
    _children.extend(node.proc for node in net.nodes)
    try:
        check(net.wait_height(2, timeout=NET_BOOT_S),
              f"the four nodes did not reach height 2: {net.heights()}")

        def submit(i: int):
            key, val = b"smoke-%d-%d" % (seed, i), b"v%d" % i
            tx = make_sig_tx(bytes([1 + i % 200]) * 32, key + b"=" + val)
            res = net.nodes[i % 4].rpc(
                "broadcast_tx_commit", {"tx": tx.hex()}, timeout=90
            )
            ok = (res["check_tx"].get("code", 0) == 0
                  and res["deliver_tx"].get("code", 0) == 0)
            return key, val, int(res["height"]), ok

        with ThreadPoolExecutor(8) as pool:
            acked = list(pool.map(submit, range(n_txs)))
        check(all(ok for *_, ok in acked),
              f"{sum(1 for *_, ok in acked if not ok)} of {n_txs} writes were refused")
        top = max(h for _, _, h, _ in acked)
        check(net.wait_height(top, timeout=60),
              f"not every node reached height {top}: {net.heights()}")

        # every acknowledged write, read back from ALL FOUR nodes
        read_back = 0
        for key, val, _, _ in acked:
            for node in net.nodes:
                res = node.rpc("abci_query", {"data": key.hex()})["response"]
                check(bytes.fromhex(res["value"]) == val,
                      f"node{node.index} answers {res['value']!r} for {key!r}")
            read_back += 1
        # at every height the four block hashes and app hashes are equal
        for h in range(1, top + 1):
            prints = {i: net.fingerprint(i, h) for i in range(4)}
            check(len(set(prints.values())) == 1,
                  f"the nodes diverge at height {h}: {prints}")
        node_sigs = []
        for node in net.nodes:
            m = node.rpc("metrics")
            check(m["gateway_verify_tpu_sigs"] > 0,
                  f"node{node.index} sent the device nothing")
            check(m["gateway_verify_breaker_state"] == 0,
                  f"node{node.index}'s breaker is in state "
                  f"{m['gateway_verify_breaker_state']}")
            node_sigs.append(int(m["gateway_verify_tpu_sigs"]))
        after = daemon_stats(sock)
        d_tpu = after["stats"]["tpu_sigs"] - before["stats"]["tpu_sigs"]
        d_cpu = after["stats"]["cpu_sigs"] - before["stats"]["cpu_sigs"]
        check(d_tpu > 0, "the daemon's tpu_sigs did not rise")
        check(d_cpu == 0, f"the daemon's cpu_sigs moved by {d_cpu}")
        # clean exit on SIGTERM
        codes = []
        for node in net.nodes:
            node.proc.send_signal(signal.SIGTERM)
        for node in net.nodes:
            codes.append(node.proc.wait(timeout=30))
        check(all(c == 0 for c in codes), f"node exit codes on SIGTERM: {codes}")
    finally:
        net.fabric.stop()
    no_jax_here()
    emit({"phase": "net-4", "ok": True, "acknowledged": len(acked),
          "read_back_from_all_four": read_back, "heights_compared": top,
          "node_tpu_sigs": node_sigs, "daemon_tpu_sigs_delta": d_tpu,
          "daemon_cpu_sigs_delta": d_cpu, "node_exit_codes": codes,
          "seconds": round(time.time() - t0, 2)})


# -- release ------------------------------------------------------------------


def phase_release(procs, socks) -> None:
    t0 = time.time()
    from tendermint_tpu import devd

    for sock in socks:
        c = devd.DevdClient(sock)
        c.shutdown()
        c.close()
    codes = [p.wait(timeout=60) for p in procs]
    check(all(c == 0 for c in codes), f"daemon exit codes: {codes}")
    check(not any(os.path.exists(s) for s in socks), "a daemon socket is left")
    emit({"phase": "release", "ok": True, "daemon_exit_codes": codes,
          "seconds": round(time.time() - t0, 2)})


# -- --chips 4 ----------------------------------------------------------------


def phase_shard(n_chips: int, lanes: int, n_keys: int, seed: int,
                deferred: list[str], rehearsal: bool) -> dict:
    """The documented multi-chip deployment (docs/device-daemon.md): N
    daemons, one per chip, their sockets in TENDERMINT_DEVD_SOCKS behind
    one gateway — compared with the same batch through ONE of them."""
    t0 = time.time()
    d = sock_dir()
    socks = [os.path.join(d, f"devd-{i}.sock") for i in range(n_chips)]
    logs = [os.path.join(OUT, f"devd-{i}.log") for i in range(n_chips)]
    procs = [start_daemon(s, lg, chip=i)
             for i, (s, lg) in enumerate(zip(socks, logs))]
    reps = wait_held(procs, socks, logs, t0 + CLAIM_S)
    for rep in reps:
        check_claim(rep, deferred, rehearsal)
        check(rep.get("device_count") == 1,
              f"a daemon sees {rep.get('device_count')} devices, not 1")
    bound = [(r.get("visible_chips"), tuple(r.get("device_ids") or ())) for r in reps]
    check(len(set(bound)) == n_chips,
          f"the daemons do not hold {n_chips} different chips: {bound}")
    emit({"phase": "claim-4", "ok": True, "seconds": round(time.time() - t0, 2),
          "daemons": [claim_line(r, time.time() - t0) for r in reps]})

    t1 = time.time()
    from tendermint_tpu import devd
    from tendermint_tpu.ops import devd_shard, gateway
    from tendermint_tpu.types import BlockID, PartSetHeader

    route_client_through(socks)
    check(devd_shard.enabled(), "the sharded device plane is not enabled")
    verifier = gateway.default_verifier()
    check(gateway.kernel_name() == "devd", "the default verifier is not on devd")
    vs, privs = make_committee(n_keys, seed)
    items: list = []
    h = 1
    while len(items) < lanes:
        bid = BlockID(bytes([h % 256]) * 20, PartSetHeader(2, b"\xbb" * 20))
        items.extend(commit_items(vs, make_commit(vs, privs, h, bid)))
        h += 1
    items = items[:lanes]
    bad = sorted({(i * 977 + 13) % lanes for i in range(7)})
    for i in bad:
        pk, msg, sig = items[i]
        items[i] = (pk, msg, sig[:9] + bytes([sig[9] ^ 1]) + sig[10:])
    want = [i not in set(bad) for i in range(lanes)]

    before = [daemon_stats(s)["stats"] for s in socks]
    ts = time.time()
    sharded = verifier.verify_batch(items)
    shard_s = time.time() - ts
    after = [daemon_stats(s) for s in socks]
    ts = time.time()
    one = devd.DevdClient(socks[0])
    single = one.verify_stream(items)
    one.close()
    single_s = time.time() - ts
    check(sharded == single, "four daemons and one daemon disagree at lanes "
          + str([i for i, (g, w) in enumerate(zip(sharded, single)) if g != w][:8]))
    check(sharded == want, "the verdicts are not the expected ones")
    deltas = [a["stats"]["tpu_sigs"] - b["tpu_sigs"]
              for a, b in zip(after, before)]
    check(all(dl > 0 for dl in deltas), f"an endpoint verified nothing: {deltas}")
    check(sum(deltas) >= lanes, f"{lanes} lanes sent, the daemons counted {deltas}")
    check(all(daemon_stats(s)["stats"]["cpu_sigs"] == 0 for s in socks),
          "a daemon answered from the host")
    eps = devd_shard.endpoint_stats()
    check(all(e["breaker_state"] == 0 for e in eps.values()),
          f"an endpoint breaker is not closed: {eps}")
    check(verifier.stats()["cpu_sigs"] == 0, "the client answered from the host")
    no_jax_here()
    emit({"phase": "shard-4", "ok": True, "lanes": lanes, "keys": n_keys,
          "rejected_lanes": bad, "endpoint_tpu_sigs_delta": deltas,
          "endpoints": {os.path.basename(k): v for k, v in eps.items()},
          "comb_pools": [a.get("comb_pool") for a in after],
          "sharded_seconds": round(shard_s, 3),
          "single_daemon_seconds": round(single_s, 3),
          "seconds": round(time.time() - t1, 2)})
    phase_release(procs, socks)
    return {"platform": reps[0].get("platform"),
            "kind": reps[0].get("device_kind"),
            "count": sum(int(r.get("device_count") or 0) for r in reps)}


# -- main ---------------------------------------------------------------------


def stop_children() -> None:
    for proc in _children:
        if proc is not None and proc.poll() is None:
            try:
                proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
    deadline = time.time() + 10
    for proc in _children:
        if proc is None:
            continue
        try:
            proc.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def run(args) -> dict:
    check(os.path.isdir(os.path.join(ROOT, "tendermint_tpu")),
          "chip_smoke.py needs the repository it sits in")
    sys.path.insert(0, ROOT)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    rehearsal = os.environ.get("TENDERMINT_DEVD_ACCEPT_CPU", "") == "1"
    deferred: list[str] = []
    if args.chips == 4:
        device = phase_shard(4, args.lanes, args.validators, args.seed,
                             deferred, rehearsal)
    else:
        phase_build()
        proc, sock, rep = phase_claim(deferred, rehearsal)
        phase_commit(sock, args.validators, args.seed)
        phase_net(sock, args.txs, args.seed)
        phase_release([proc], [sock])
        device = {"platform": rep.get("platform"),
                  "kind": rep.get("device_kind"),
                  "count": rep.get("device_count")}
    no_jax_here()
    check(not deferred, "; ".join(deferred))
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: four daemons, one per chip, behind one gateway")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--validators", type=int, default=1000,
                    help="committee size (BASELINE.json config 4: 1000)")
    ap.add_argument("--txs", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=8192,
                    help="--chips 4: lanes of the one sharded batch")
    args = ap.parse_args()

    def on_alarm(signum, frame):
        raise SmokeFailure(f"the run passed its bound of {WATCHDOG_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(WATCHDOG_S)
    try:
        device = run(args)
    except BaseException as exc:  # noqa: BLE001 — every failure: exit != 0
        stop_children()
        emit({"ok": False, "error": f"{type(exc).__name__}: {exc}"[:4000]})
        return 1
    finally:
        signal.alarm(0)
    stop_children()
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
