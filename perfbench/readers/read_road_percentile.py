"""A percentile, ms, over the window's reads, of one piece of a read's
road from the generator to the node's proof and back.

The program traces 1 in N reads (`abci_query`) on every node, by the
node's own count of reads (`tendermint_tpu/libs/txtrace.py`), keyed by
the request's JSON-RPC id; each node's flight-recorder dump with reason
`stop` carries them (`query_traces`). This joins the generator's
answered reads of the window (`loadgen.out`: operation i went to node
`node[i]` with JSON-RPC id i, `sent`, `done`) with the traces of the
node that answered, by (node, id):

    edge     done - sent, less the node's rpc_ingress -> rpc_reply
             (connect, accept, the handler's thread, loopback, parse)
    lock     rpc_ingress -> app_lock (the proxy's app mutex: a read
             waits here behind a block's apply)
    prove    app_lock -> proved (the tree's lock and the proof's walk)
    reply    proved -> rpc_reply (the proof's encoding, hex and JSON,
             the socket)

For every joined read the four pieces sum to its own done - sent (the
generator's clock gives the first term's difference, the node's wall
clock the rest). params: {"piece": one of PIECES, "q": 0..100}. Nothing
from a program whose dumps carry no read traces, or where fewer than
MIN_JOINED reads join.
"""

from __future__ import annotations

import json
import os
import re

from harness import artifacts
from harness.observe import quantile

PIECES = ("edge", "lock", "prove", "reply")
STAMPS = ("rpc_ingress", "app_lock", "proved", "rpc_reply")
MIN_JOINED = 50


def roads(obs) -> list[dict] | None:
    """One dict of the four pieces and their `total` (ms) per joined
    read, once a run."""
    if "read_roads" in obs.trace:
        return obs.trace["read_roads"]
    obs.trace["read_roads"] = None
    if not artifacts.program_keeps_records():
        return None
    run = artifacts.run_dir(obs)
    traces: dict[int, dict] = {}
    for d in os.listdir(run):
        m = re.fullmatch(r"node(\d+)", d)
        if not m:
            continue
        with open(artifacts.stop_dump(run, int(m.group(1)))) as f:
            dumped = json.load(f).get("query_traces")
        if dumped is None:
            return None  # a program from before the read traces
        traces[int(m.group(1))] = {t["id"]: t["stages"] for t in dumped}
    with open(os.path.join(run, "loadgen.out")) as f:
        lg = json.load(f)
    out = []
    for i in range(lg["lead_in_operations"], len(lg["kind"])):
        if lg["kind"][i] != "read" or not lg["ok"][i]:
            continue
        st = traces.get(lg["node"][i], {}).get(i)
        if st is None or any(k not in st for k in STAMPS):
            continue
        total = lg["done"][i] - lg["sent"][i]
        out.append({k: 1000.0 * v for k, v in (
            ("edge", total - (st["rpc_reply"] - st["rpc_ingress"])),
            ("lock", st["app_lock"] - st["rpc_ingress"]),
            ("prove", st["proved"] - st["app_lock"]),
            ("reply", st["rpc_reply"] - st["proved"]),
            ("total", total))})
    obs.trace["read_roads"] = out
    return out


def read(obs, params, device):
    joined = roads(obs)
    if joined is None or len(joined) < MIN_JOINED:
        return None
    return quantile([r[params["piece"]] for r in joined],
                    float(params["q"]) / 100.0)
