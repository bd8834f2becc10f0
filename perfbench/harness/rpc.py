"""JSON-RPC over HTTP to a node's public RPC port, stdlib only."""

from __future__ import annotations

import http.client
import json
import time


class RPCFailure(Exception):
    pass


def call(addr: tuple[str, int], method: str, params: dict | None = None,
         timeout: float = 10.0):
    body = json.dumps({"jsonrpc": "2.0", "id": "perfbench",
                       "method": method, "params": params or {}})
    conn = http.client.HTTPConnection(addr[0], addr[1], timeout=timeout)
    try:
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    try:
        out = json.loads(raw)
    except ValueError:
        raise RPCFailure(f"{method}: HTTP {resp.status} {raw[:200]!r}")
    if out.get("error"):
        raise RPCFailure(f"{method}: {out['error']}")
    return out["result"]


def height(addr) -> int:
    try:
        return int(call(addr, "status", timeout=5)["latest_block_height"])
    except (OSError, RPCFailure, KeyError, ValueError):
        return -1


def wait_heights(addrs, h: int, deadline: float, alive=None) -> bool:
    while time.time() < deadline:
        if alive is not None:
            alive()
        if all(height(a) >= h for a in addrs):
            return True
        time.sleep(0.2)
    return False


def metrics(addr) -> dict:
    """The flat legacy gauge dict of the `metrics` RPC. A node under
    pressure sheds reads for a moment (`shed:shed_reads`): ask again."""
    for attempt in range(5):
        try:
            return call(addr, "metrics", timeout=10)
        except RPCFailure as exc:
            if "shed" not in str(exc) or attempt == 4:
                raise
            time.sleep(0.3)
    raise AssertionError("unreachable")


def prom_sum(addr, family: str) -> float:
    """Sum of every series of one family on the node's Prometheus page
    (GET /metrics), e.g. p2p_peer_recv_bytes_total over peers and
    channels."""
    conn = http.client.HTTPConnection(addr[0], addr[1], timeout=10)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    total = 0.0
    for line in text.splitlines():
        if line.startswith(family) and line[len(family):len(family) + 1] in ("{", " "):
            try:
                total += float(line.rsplit(" ", 1)[1])
            except (IndexError, ValueError):
                pass
    return total
