#!/usr/bin/env python3
"""The load generator for `ycsb_core` traffic, a process of its own (no
node, no JAX in it): one asyncio loop, one thread, `loadgen.py`'s open
loop (its arrival offsets, its HTTP post, its files) over YCSB's
operations.

Operation i of the seed (`harness/ycsb.draw_operations`) is a read or an
update of one record. An update is `broadcast_tx_commit` of
pubkey || signature || key=value, the record's ten fields rewritten and
signed with THE RECORD'S key; a read is `abci_query` of the record's key
with `prove` true. Each is sent at its due instant whatever the system
does and timed from that DUE instant until the answer is read. Operation
i goes to RPC port i mod 4.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import sys
import time


async def one_op(i: int, op: dict, target, due_mono: float, timeout: float,
                 rec: dict, post) -> None:
    delay = due_mono - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    sent = time.monotonic()
    ok, height, err, got = False, 0, "", None
    try:
        out = await post(target[0], target[1], op["body"], timeout)
        if out.get("error"):
            err = str(out["error"])[:200]
        elif op["kind"] == "update":
            res = out["result"]
            ok = ((res.get("check_tx") or {}).get("code", 1) == 0
                  and (res.get("deliver_tx") or {}).get("code", 1) == 0)
            height = int(res.get("height") or 0)
            if not ok:
                # the two codes and logs whole, the judge reads them
                err = json.dumps({k: {"code": (res.get(k) or {}).get("code"),
                                      "log": str((res.get(k) or {}).get("log")
                                                 or "")[:80]}
                                  for k in ("check_tx", "deliver_tx")})
        else:
            res = out["result"]["response"]
            ok = res.get("code", 1) == 0
            height = int(res.get("height") or 0)
            value = bytes.fromhex(res.get("value") or "")
            got = [len(value), hashlib.sha256(value).hexdigest()]
            if not ok:
                err = json.dumps(res)[:200]
    except Exception as exc:  # noqa: BLE001 — a failed operation is a result
        err = f"{type(exc).__name__}: {exc}"[:200]
    rec["sent"][i] = sent
    rec["done"][i] = time.monotonic()
    rec["ok"][i] = ok
    rec["height"][i] = height
    rec["err"][i] = err
    rec["got"][i] = got


def prepare(p: dict, n: int) -> list[dict]:
    from harness import ycsb

    seed = int(p["seed"])
    keypair = ycsb.make_keypair()
    ops = []
    for i, (kind, record) in enumerate(ycsb.draw_operations(
            seed, n, int(p["recordcount"]), float(p["read_share"]),
            float(p["zipfian_constant"]))):
        key = ycsb.record_key(record)
        if kind == "update":
            payload = key + b"=" + ycsb.record_value(seed, record, i + 1)
            pub, sign = keypair(ycsb.record_secret(seed, record))
            tx = pub + sign(payload) + payload
            call = {"method": "broadcast_tx_commit", "params": {"tx": tx.hex()}}
        else:
            tx = b""
            call = {"method": "abci_query",
                    "params": {"data": key.hex(), "prove": True}}
        ops.append({"kind": kind, "record": record, "tx": tx,
                    "body": json.dumps({"jsonrpc": "2.0", "id": i,
                                        **call}).encode()})
    return ops


async def run(p: dict) -> dict:
    from harness.loadgen import arrival_offsets, post

    seconds, rate = float(p["seconds"]), float(p["rate_per_s"])
    lead = float(p.get("lead_in_s", 0.0))
    targets = [tuple(t) for t in p["targets"]]
    if p["arrivals"] != "exponential":
        raise ValueError(f"unknown arrivals {p['arrivals']!r}")
    offs = arrival_offsets(rate, seconds, int(p["seed"]))
    n_lead = int(round(rate * lead))
    lead_offs = [-lead + k / rate for k in range(n_lead)]
    ops = prepare(p, n_lead + len(offs))
    with open(p["ready_file"] + ".tmp", "w") as f:
        json.dump({"operations": len(offs), "lead_in_operations": n_lead}, f)
    os.replace(p["ready_file"] + ".tmp", p["ready_file"])
    while not os.path.exists(p["start_file"]):
        await asyncio.sleep(0.01)
    open_mono = time.monotonic() + lead + 0.2
    open_wall = time.time() + (open_mono - time.monotonic())
    with open(p["window_file"] + ".tmp", "w") as f:
        json.dump({"open_wall": open_wall, "close_wall": open_wall + seconds}, f)
    os.replace(p["window_file"] + ".tmp", p["window_file"])
    n = len(ops)
    rec = {k: [None] * n for k in ("sent", "done", "ok", "height", "err", "got")}
    dues = [open_mono + o for o in lead_offs + offs]
    timeout = float(p.get("request_timeout_s", 60))
    await asyncio.gather(*[
        one_op(i, ops[i], targets[i % len(targets)], dues[i], timeout, rec, post)
        for i in range(n)])
    return {
        "open_wall": open_wall, "seconds": seconds,
        "lead_in_operations": n_lead,
        "kind": [o["kind"] for o in ops],
        "record": [o["record"] for o in ops],
        "node": [i % len(targets) for i in range(n)],
        "due": [d - open_mono for d in dues],
        "sent": [s - open_mono for s in rec["sent"]],
        "done": [d - open_mono for d in rec["done"]],
        "ok": rec["ok"], "height": rec["height"], "err": rec["err"],
        "got": rec["got"],
        "tx": [o["tx"].hex() for o in ops],
    }


def main() -> None:
    with open(sys.argv[1]) as f:
        p = json.load(f)
    sys.path.insert(0, p["root"])
    sys.path.insert(0, p["bench_dir"])
    out = asyncio.run(run(p))
    with open(p["out_file"] + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(p["out_file"] + ".tmp", p["out_file"])


if __name__ == "__main__":
    main()
