#!/usr/bin/env python3
"""The general load generator for `open_loop_writes` traffic, a process
of its own (no node, no relay, no JAX in it): one asyncio loop, one
thread.

It reads one parameter file (made by the harness from the traffic mix's
data file, the seed and the node addresses), prepares every write before
the window, waits for the harness's start file, then sends each write at
its due instant whatever the system does (open loop) and times it from
that DUE instant until the RPC answer is read. Results go to one JSON
file.

Every seed gets the same multiset of arrival gaps and the same number of
writes, in an order drawn from the run's seed: the gaps are the n
quantiles of the exponential distribution, scaled to fill the window
exactly, and shuffled by the seed.
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import random
import sys
import time


def arrival_offsets(rate: float, seconds: float, seed: int) -> list[float]:
    """Due instants in [0, seconds), as offsets from the window's open:
    exponential gaps (a Poisson stream of independent users)."""
    n = int(round(rate * seconds))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (k + 0.5) / n) for k in range(n)]
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)          # the first write is due at the open
        t += g
    return out


def make_writes(seed: int, n: int, n_signers: int, root: str,
                tag: bytes = b"w") -> list[dict]:
    """n signed writes of fresh keys: pubkey || signature || key=value."""
    sys.path.insert(0, root)
    from harness.chain import derive, make_signer

    from tendermint_tpu.crypto import ed25519 as ed

    make = make_signer()
    secrets = [derive(seed, "signer", k) for k in range(n_signers)]
    pubs = [ed.public_key(s) for s in secrets]
    signers = [make(s) for s in secrets]
    out = []
    for i in range(n):
        k = i % n_signers
        key = b"%s%d-%d" % (tag, seed % 1000003, i)
        val = b"v%d" % i
        payload = key + b"=" + val
        out.append({"key": key, "value": val,
                    "tx": pubs[k] + signers[k](payload) + payload})
    return out


async def post(host: str, port: int, body: bytes, timeout: float) -> dict:
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection(host, port), timeout)
    try:
        writer.write(
            b"POST / HTTP/1.1\r\nHost: %s\r\nContent-Type: application/json"
            b"\r\nConnection: close\r\nContent-Length: %d\r\n\r\n"
            % (host.encode(), len(body)) + body)
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout)
    finally:
        writer.close()
    _head, _, payload = raw.partition(b"\r\n\r\n")
    return json.loads(payload)


async def one_write(i: int, w: dict, target, due_mono: float, timeout: float,
                    rec: dict) -> None:
    delay = due_mono - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    sent = time.monotonic()
    body = json.dumps({"jsonrpc": "2.0", "id": i,
                       "method": "broadcast_tx_commit",
                       "params": {"tx": w["tx"].hex()}}).encode()
    ok, height, err = False, 0, ""
    try:
        out = await post(target[0], target[1], body, timeout)
        if out.get("error"):
            err = str(out["error"])[:200]
        else:
            res = out["result"]
            ok = ((res.get("check_tx") or {}).get("code", 1) == 0
                  and (res.get("deliver_tx") or {}).get("code", 1) == 0)
            height = int(res.get("height") or 0)
            if not ok:
                err = json.dumps(res)[:200]
    except Exception as exc:  # noqa: BLE001 — a failed write is a result
        err = f"{type(exc).__name__}: {exc}"[:200]
    done = time.monotonic()
    rec["sent"][i] = sent
    rec["done"][i] = done
    rec["ok"][i] = ok
    rec["height"][i] = height
    rec["err"][i] = err


async def run(p: dict) -> dict:
    seed, seconds, rate = int(p["seed"]), float(p["seconds"]), float(p["rate_per_s"])
    lead = float(p.get("lead_in_s", 0.0))
    targets = [tuple(t) for t in p["targets"]]
    if p["arrivals"] != "exponential":
        raise ValueError(f"unknown arrivals {p['arrivals']!r}")
    offs = arrival_offsets(rate, seconds, seed)
    n_lead = int(round(rate * lead))
    lead_offs = [-lead + k / rate for k in range(n_lead)]
    writes = make_writes(seed, n_lead + len(offs), int(p["signers"]), p["bench_dir"])
    with open(p["ready_file"] + ".tmp", "w") as f:
        json.dump({"writes": len(offs), "lead_in_writes": n_lead}, f)
    os.replace(p["ready_file"] + ".tmp", p["ready_file"])
    while not os.path.exists(p["start_file"]):
        await asyncio.sleep(0.01)
    open_mono = time.monotonic() + lead + 0.2
    open_wall = time.time() + (open_mono - time.monotonic())
    with open(p["window_file"] + ".tmp", "w") as f:
        json.dump({"open_wall": open_wall, "close_wall": open_wall + seconds}, f)
    os.replace(p["window_file"] + ".tmp", p["window_file"])
    n = len(writes)
    rec = {k: [None] * n for k in ("sent", "done", "ok", "height", "err")}
    dues = [open_mono + o for o in lead_offs + offs]
    timeout = float(p.get("request_timeout_s", 60))
    tasks = [asyncio.ensure_future(
        one_write(i, writes[i], targets[i % len(targets)], dues[i], timeout, rec))
        for i in range(n)]
    await asyncio.gather(*tasks)
    return {
        "open_wall": open_wall, "seconds": seconds,
        "lead_in_writes": n_lead,
        "due": [d - open_mono for d in dues],
        "sent": [s - open_mono for s in rec["sent"]],
        "done": [d - open_mono for d in rec["done"]],
        "ok": rec["ok"], "height": rec["height"], "err": rec["err"],
        "node": [i % len(targets) for i in range(n)],
        "key": [w["key"].hex() for w in writes],
        "value": [w["value"].hex() for w in writes],
        "tx": [w["tx"].hex() for w in writes],
    }


def main() -> None:
    with open(sys.argv[1]) as f:
        p = json.load(f)
    out = asyncio.run(run(p))
    with open(p["out_file"] + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(p["out_file"] + ".tmp", p["out_file"])


if __name__ == "__main__":
    main()
