#!/usr/bin/env python3
"""The load generator for `ycsb_core` traffic whose reads are judged by
their proofs (deployment `ycsb_verified_net`): `ycsb_loadgen.py`'s
operations, open loop, HTTP post and timing, operation for operation,
with two differences.

- The operations of a seeded sample of `proof_sample` of the window's
  reads keep the value and the proof their answer carried (`proofs`:
  operation -> [value hex, proof hex]), for the judge to check against
  the app hash of the committed header.
- One process a target: share k sends the operations i with i mod
  (number of targets) = k, which are target k's, so each asyncio loop
  carries one port's share of the rate (one loop is late at B's rate:
  `PERF.md` section 6). Share 0 is the process the scenario starts; it
  starts the others, holds the ready / start / window handshake, and
  writes the one result file, as one process would.

    python3 ycsb_proof_loadgen.py <params.json>   (share 0: the others
                                                   are started by it)
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import subprocess
import sys
import time


def proof_sample(p: dict, kinds: list[str], n_lead: int) -> set[int]:
    """The window reads whose proofs are kept, drawn from the seed."""
    reads = [i for i in range(n_lead, len(kinds)) if kinds[i] == "read"]
    rng = random.Random(int(p["seed"]) ^ 0x9E0F5)
    return set(rng.sample(reads, min(len(reads), int(p["proof_sample"]))))


def _write(path: str, obj) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


async def _wait_file(path: str, parent: int = 0) -> None:
    while not os.path.exists(path):
        if parent and os.getppid() != parent:
            raise SystemExit("the generator's first share is gone")
        await asyncio.sleep(0.01)


async def run_share(p: dict, share: int, children=()) -> dict:
    from harness.loadgen import arrival_offsets, post
    from harness.ycsb_loadgen import one_op, prepare

    seconds, rate = float(p["seconds"]), float(p["rate_per_s"])
    lead = float(p.get("lead_in_s", 0.0))
    targets = [tuple(t) for t in p["targets"]]
    processes = len(targets)
    if p["arrivals"] != "exponential":
        raise ValueError(f"unknown arrivals {p['arrivals']!r}")
    offs = arrival_offsets(rate, seconds, int(p["seed"]))
    n_lead = int(round(rate * lead))
    lead_offs = [-lead + k / rate for k in range(n_lead)]
    ops = prepare(p, n_lead + len(offs))
    kept = proof_sample(p, [o["kind"] for o in ops], n_lead)
    if share == 0:
        for k in range(1, processes):
            await _wait_file(f"{p['ready_file']}.{k}")
        _write(p["ready_file"], {"operations": len(offs),
                                 "lead_in_operations": n_lead})
        await _wait_file(p["start_file"])
        open_mono = time.monotonic() + lead + 0.2
        open_wall = time.time() + (open_mono - time.monotonic())
        _write(p["window_file"], {"open_wall": open_wall,
                                  "close_wall": open_wall + seconds})
    else:
        _write(f"{p['ready_file']}.{share}", {})
        await _wait_file(p["window_file"], parent=int(p["parent_pid"]))
        with open(p["window_file"]) as f:
            open_wall = json.load(f)["open_wall"]
        open_mono = time.monotonic() + (open_wall - time.time())
    n = len(ops)
    mine = range(share, n, processes)
    rec = {k: [None] * n for k in ("sent", "done", "ok", "height", "err", "got")}
    proofs: dict[int, list] = {}

    def keeping(i: int):
        async def post_keeping(host, port, body, timeout):
            out = await post(host, port, body, timeout)
            res = (out.get("result") or {}).get("response") or {}
            proofs[i] = [res.get("value") or "", res.get("proof") or ""]
            return out
        return post_keeping

    dues = [open_mono + o for o in lead_offs + offs]
    timeout = float(p.get("request_timeout_s", 60))
    await asyncio.gather(*[
        one_op(i, ops[i], targets[share], dues[i], timeout, rec,
               keeping(i) if i in kept else post)
        for i in mine])
    out = {
        "open_wall": open_wall, "seconds": seconds,
        "lead_in_operations": n_lead,
        "kind": [o["kind"] for o in ops],
        "record": [o["record"] for o in ops],
        "node": [i % processes for i in range(n)],
        "due": [d - open_mono for d in dues],
        "sent": [None if s is None else s - open_mono for s in rec["sent"]],
        "done": [None if d is None else d - open_mono for d in rec["done"]],
        "ok": rec["ok"], "height": rec["height"], "err": rec["err"],
        "got": rec["got"],
        "tx": [o["tx"].hex() for o in ops],
        "proof_sample": sorted(kept),
        "proofs": {str(i): v for i, v in proofs.items()},
    }
    for k, child in enumerate(children, start=1):
        await asyncio.get_running_loop().run_in_executor(None, child.wait)
        if child.returncode != 0:
            raise RuntimeError(f"generator share {k} exited {child.returncode}")
        with open(f"{p['out_file']}.{k}") as f:
            part = json.load(f)
        for i in range(k, n, processes):
            for key in ("sent", "done", "ok", "height", "err", "got"):
                out[key][i] = part[key][i]
        out["proofs"].update(part["proofs"])
    return out


def main() -> None:
    with open(sys.argv[1]) as f:
        p = json.load(f)
    sys.path.insert(0, p["root"])
    sys.path.insert(0, p["bench_dir"])
    share = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    p["parent_pid"] = int(sys.argv[3]) if share else os.getpid()
    children = [] if share else [
        subprocess.Popen([sys.executable, os.path.abspath(__file__),
                          sys.argv[1], str(k), str(os.getpid())])
        for k in range(1, len(p["targets"]))]
    try:
        out = asyncio.run(run_share(p, share, children))
    finally:
        for child in children:
            if child.poll() is None:
                child.kill()
    _write(p["out_file"] + (f".{share}" if share else ""), out)


if __name__ == "__main__":
    main()
