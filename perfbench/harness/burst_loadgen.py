#!/usr/bin/env python3
"""The load generator of `burst_writes` traffic, a process of its own (no
node, no JAX in it): one asyncio loop, one thread.

It reads one parameter file, signs every write before the window, and at
the harness's start opens a `NewBlock` subscription on every node's
websocket, then:
- the lead-in: writes at a steady rate, each sent at its due instant on a
  connection of its own (not sampled);
- at the window's open, the burst: every write due at that instant (or,
  with several `bursts_at_s`, the burst in that many equal parts, each
  due at its offset), handed over by `connections_per_node` keep-alive
  connections a node, opened during the lead-in (write j goes to node
  j mod n); a connection sends its next write as soon as the last one's
  `broadcast_tx_sync` answer (the CheckTx verdict) is read.

A write's commit instant is when the `NewBlock` event of the block that
holds it arrives from the node it was sent to. The generator stops at the
window's close plus `answer_after_close_s`; a write with no commit
instant by then is unanswered. Results go to one JSON file.

`plan(seed, n, signers)` is the burst: write j sets `b<seed>-<j>` to
`v<j>`, signed by signer j mod `signers`; six pairs of writes, each pair
four apart (consecutive in one node's queue) and each write between two
valid ones, are forged (none in a burst under 64 writes): the first of a
pair with a signature bit flipped, the second with its last payload byte
altered. `reference/burst_ref.py` draws the same from the seed alone.
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import random
import re
import sys
import time
from collections import deque

FORGED_PAIRS = 6


def forged_places(seed: int, n: int) -> dict[int, int]:
    """Six pairs (p, p + 4) of burst positions, each write with a valid
    one on either side: position -> 0 (signature) or 1 (message)."""
    rng = random.Random(seed ^ 0xB0257)
    taken: set[int] = set()
    out: dict[int, int] = {}
    while len(out) < 2 * FORGED_PAIRS:
        p = rng.randrange(1, n - 5)
        around = {p - 1, p, p + 1, p + 3, p + 4, p + 5}
        if around & taken:
            continue
        taken |= around
        out[p], out[p + 4] = 0, 1
    return out


def plan(seed: int, n: int, n_signers: int, root: str) -> tuple[list, list]:
    """(burst writes, forged positions). A write: key, value, tx."""
    sys.path.insert(0, root)
    from harness.chain import derive, make_signer

    from tendermint_tpu.crypto import ed25519 as ed

    make = make_signer()
    secrets = [derive(seed, "signer", k) for k in range(n_signers)]
    pubs = [ed.public_key(s) for s in secrets]
    signers = [make(s) for s in secrets]
    kind = forged_places(seed, n) if n >= 64 else {}
    out = []
    for j in range(n):
        k = j % n_signers
        key = b"b%d-%d" % (seed % 1000003, j)
        val = b"v%d" % j
        payload = key + b"=" + val
        tx = bytearray(pubs[k] + signers[k](payload) + payload)
        if j in kind:
            if kind[j] == 0:
                tx[32 + 5] ^= 0x40                  # the signature
            else:
                tx[-1] ^= 0x01                      # the message
        out.append({"key": key, "value": val, "tx": bytes(tx)})
    return out, sorted(kind)


def lead_writes(seed: int, n: int, n_signers: int, root: str) -> list:
    from harness.chain import derive, make_signer

    from tendermint_tpu.crypto import ed25519 as ed

    make = make_signer()
    out = []
    for i in range(n):
        s = derive(seed, "signer", i % n_signers)
        payload = b"l%d-%d=v%d" % (seed % 1000003, i, i)
        out.append({"key": payload.split(b"=")[0], "value": b"v%d" % i,
                    "tx": ed.public_key(s) + make(s)(payload) + payload})
    return out


# -- the wire ------------------------------------------------------------------


def _body(i: int, tx: bytes) -> bytes:
    return json.dumps({"jsonrpc": "2.0", "id": i, "method": "broadcast_tx_sync",
                       "params": {"tx": tx.hex()}}).encode()


async def _exchange(reader, writer, host: str, body: bytes, timeout: float,
                    keep: bool) -> tuple[dict, bool]:
    """One request on an open connection; (answer, server closes)."""
    writer.write(b"POST / HTTP/1.1\r\nHost: %s\r\nContent-Type: application/json"
                 b"\r\nConnection: %s\r\nContent-Length: %d\r\n\r\n"
                 % (host.encode(), b"keep-alive" if keep else b"close",
                    len(body)) + body)
    await writer.drain()
    head = await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"), timeout)
    m = re.search(rb"(?i)\r\ncontent-length:\s*(\d+)", head)
    payload = await asyncio.wait_for(
        reader.readexactly(int(m.group(1))) if m else reader.read(), timeout)
    closes = re.search(rb"(?i)\r\nconnection:\s*close", head) is not None
    return json.loads(payload), closes


def _verdict(out: dict) -> tuple[int | None, str, str]:
    """(CheckTx code, its log or the RPC error, the tx hash the node
    named): code None where the RPC answered with an error."""
    if out.get("error"):
        return None, str(out["error"])[:200], ""
    res = out["result"]
    return (int(res.get("code", 1)), str(res.get("log") or "")[:200],
            str(res.get("hash") or ""))


async def subscribe(host: str, port: int, node: int, events: list,
                    ready: asyncio.Event) -> None:
    """`NewBlock` events of one node until cancelled: (node, height,
    arrival, the block's txs upper-case hex)."""
    reader, writer = await asyncio.open_connection(host, port, limit=1 << 26)
    key = base64.b64encode(os.urandom(16))
    writer.write(b"GET /websocket HTTP/1.1\r\nHost: %s:%d\r\nUpgrade: websocket"
                 b"\r\nConnection: Upgrade\r\nSec-WebSocket-Key: %s\r\n"
                 b"Sec-WebSocket-Version: 13\r\n\r\n" % (host.encode(), port, key))
    await writer.drain()
    head = await reader.readuntil(b"\r\n\r\n")
    if b" 101 " not in head.split(b"\r\n", 1)[0]:
        raise RuntimeError(f"node{node}: no websocket: {head[:200]!r}")
    msg = json.dumps({"jsonrpc": "2.0", "id": "nb", "method": "subscribe",
                      "params": {"event": "NewBlock"}}).encode()
    mask = os.urandom(4)
    frame = bytes([0x81, 0x80 | 126]) + len(msg).to_bytes(2, "big") + mask \
        + bytes(c ^ mask[i % 4] for i, c in enumerate(msg))
    writer.write(frame)
    await writer.drain()
    try:
        while True:
            b1, b2 = await reader.readexactly(2)
            n = b2 & 0x7F
            if n == 126:
                n = int.from_bytes(await reader.readexactly(2), "big")
            elif n == 127:
                n = int.from_bytes(await reader.readexactly(8), "big")
            payload = await reader.readexactly(n)
            at = time.monotonic()
            if b1 & 0x0F != 0x1:
                continue
            res = (json.loads(payload).get("result") or {})
            if res.get("event") != "NewBlock":
                ready.set()                       # the subscription's answer
                continue
            blk = res["data"]["block"]
            events.append((node, int(blk["header"]["height"]), at,
                           [t.upper() for t in (blk["data"]["txs"] or [])]))
    finally:
        writer.close()


async def lead_one(i: int, w: dict, target, due: float, timeout: float,
                   rec: dict) -> None:
    delay = due - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["sent"][i] = time.monotonic()
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(*target), timeout)
        try:
            out, _ = await _exchange(reader, writer, target[0], _body(i, w["tx"]),
                                     timeout, keep=False)
        finally:
            writer.close()
        rec["code"][i], rec["err"][i], rec["hash"][i] = _verdict(out)
    except Exception as exc:  # noqa: BLE001 — a failed write is a result
        rec["err"][i] = f"{type(exc).__name__}: {exc}"[:200]
    rec["checked"][i] = time.monotonic()


async def connection(queue: deque, target, timeout: float, rec: dict,
                     writes: list, dues: list, open_after: float) -> None:
    """One client connection of the burst, opened `open_after` seconds
    into the lead-in (a client's pool is open before it hands a batch
    over): the node's next write as soon as the last one's answer is read
    (and the write is due); a lost connection is opened anew (the write
    it was carrying failed)."""
    await asyncio.sleep(open_after)
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(*target), timeout)
    except (OSError, asyncio.TimeoutError):
        reader = writer = None          # opened again for the first write
    try:
        while queue:
            i = queue.popleft()
            delay = dues[i] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            rec["sent"][i] = time.monotonic()
            try:
                if writer is None:
                    reader, writer = await asyncio.wait_for(
                        asyncio.open_connection(*target), timeout)
                out, closes = await _exchange(reader, writer, target[0],
                                              _body(i, writes[i]["tx"]), timeout,
                                              keep=True)
                rec["code"][i], rec["err"][i], rec["hash"][i] = _verdict(out)
                if closes:
                    writer.close()
                    writer = None
            except Exception as exc:  # noqa: BLE001 — a failed write
                rec["err"][i] = f"{type(exc).__name__}: {exc}"[:200]
                if writer is not None:
                    writer.close()
                writer = None
            rec["checked"][i] = time.monotonic()
    finally:
        if writer is not None:
            writer.close()


async def run(p: dict) -> dict:
    seed, seconds = int(p["seed"]), float(p["seconds"])
    targets = [tuple(t) for t in p["targets"]]
    n_nodes = len(targets)
    lead, lead_rate = float(p["lead_in_s"]), float(p["lead_in_rate_per_s"])
    n_lead = int(round(lead * lead_rate))
    burst, forged = plan(seed, int(p["burst_writes"]), int(p["signers"]),
                         p["bench_dir"])
    writes = lead_writes(seed, n_lead, int(p["signers"]), p["bench_dir"]) + burst
    n = len(writes)
    with open(p["ready_file"] + ".tmp", "w") as f:
        json.dump({"writes": n - n_lead, "lead_in_writes": n_lead}, f)
    os.replace(p["ready_file"] + ".tmp", p["ready_file"])
    while not os.path.exists(p["start_file"]):
        await asyncio.sleep(0.01)
    # the nodes are up: every subscription answered before the lead-in
    events: list = []
    subs, readies = [], []
    for k, t in enumerate(targets):
        ready = asyncio.Event()
        readies.append(ready)
        subs.append(asyncio.ensure_future(subscribe(t[0], t[1], k, events, ready)))
    await asyncio.wait_for(asyncio.gather(*(r.wait() for r in readies)), 60)
    open_mono = time.monotonic() + lead + 0.2
    open_wall = time.time() + (open_mono - time.monotonic())
    with open(p["window_file"] + ".tmp", "w") as f:
        json.dump({"open_wall": open_wall, "close_wall": open_wall + seconds}, f)
    os.replace(p["window_file"] + ".tmp", p["window_file"])
    rec = {k: [None] * n for k in ("sent", "checked", "code", "err", "hash")}
    timeout = float(p["request_timeout_s"])
    # the burst in len(bursts_at_s) equal parts in its order, each due at
    # its offset from the open (one part, due at the open, as shipped)
    at = [float(x) for x in p["bursts_at_s"]]
    dues = [open_mono - lead + k / lead_rate for k in range(n_lead)] \
        + [open_mono + at[j * len(at) // (n - n_lead)] for j in range(n - n_lead)]
    node = [i % n_nodes for i in range(n_lead)] \
        + [j % n_nodes for j in range(n - n_lead)]
    tasks = [asyncio.ensure_future(lead_one(i, writes[i], targets[node[i]],
                                            dues[i], timeout, rec))
             for i in range(n_lead)]
    # the burst's connections, opened one after another during the lead-in
    queues = [deque(i for i in range(n_lead, n) if node[i] == k)
              for k in range(n_nodes)]
    per = int(p["connections_per_node"])
    tasks += [asyncio.ensure_future(connection(
        queues[k], targets[k], timeout, rec, writes, dues,
        0.01 * (c * n_nodes + k)))
        for k in range(n_nodes) for c in range(per)]
    await asyncio.gather(*tasks)
    # the commit instants: wait for the events of every acknowledged write
    stop_at = open_mono + seconds + float(p["answer_after_close_s"])
    want = {writes[i]["tx"].hex().upper(): i for i in range(n) if rec["code"][i] == 0}
    committed: list = [None] * n
    height: list = [0] * n
    seen = 0
    while True:
        for k_node, h, at, txs in events[seen:]:
            for t in txs:
                i = want.get(t)
                if i is not None and node[i] == k_node and committed[i] is None:
                    committed[i], height[i] = at, h
        seen = len(events)
        if all(committed[i] is not None for i in want.values()) \
                or time.monotonic() >= stop_at:
            break
        await asyncio.sleep(0.05)
    for s in subs:
        s.cancel()
    await asyncio.gather(*subs, return_exceptions=True)
    blocks = {}
    for k_node, h, at, txs in events:
        if k_node == 0:
            blocks[str(h)] = {"arrival": at - open_mono, "txs": len(txs)}
    return {
        "open_wall": open_wall, "seconds": seconds, "lead_in_writes": n_lead,
        "forged": [n_lead + j for j in forged],
        "due": [d - open_mono for d in dues],
        "sent": [None if s is None else s - open_mono for s in rec["sent"]],
        "checked": [None if s is None else s - open_mono for s in rec["checked"]],
        "code": rec["code"], "err": rec["err"], "hash": rec["hash"],
        "committed": [None if c is None else c - open_mono for c in committed],
        "height": height, "node": node,
        "key": [w["key"].hex() for w in writes],
        "value": [w["value"].hex() for w in writes],
        "tx": [w["tx"].hex() for w in writes],
        "node0_blocks": blocks,
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
