"""A percentile, ms, over the window's writes, of one piece of a write's
road from the RPC port to its reply (PR 37).

The program traces 1 in N writes on EVERY node by a hash of their bytes
(`tendermint_tpu/libs/txtrace.py`) and each node's flight-recorder dump
with reason `stop` carries them (`tx_traces`). This joins, by tx hash,
the generator's acknowledged writes of the window (`loadgen.out`: `tx`,
`sent`, `done`, the `node` it was sent to; on a YCSB cell its updates
alone: a read is an `abci_query`) with the traces of A, the node the
write was sent to, and of P, the node that stamped `reap` (the proposer
of the block that committed it):

    edge             done - sent, less A's rpc_reply - rpc_ingress
    gate             A's rpc_ingress -> A's sig_gate
    to_proposer      A's sig_gate -> P's mempool_admit
    await_reap       P's mempool_admit -> P's reap
    reap_to_commit   P's reap -> A's block_commit
    commit_to_reply  A's block_commit -> A's rpc_reply

For every joined write the six pieces sum to its own done - sent (the
generator's clock gives the first two terms' difference, the nodes'
wall clock the rest: one host's CLOCK_REALTIME). params: {"piece": one of
PIECES, "q": 0..100}. Nothing from a program whose dumps carry no tx
traces, or where fewer than MIN_JOINED writes join.
"""

from __future__ import annotations

import json
import os
import re

from harness import artifacts
from harness.observe import quantile

PIECES = ("edge", "gate", "to_proposer", "await_reap", "reap_to_commit",
          "commit_to_reply")
MIN_JOINED = 50


def _joined(obs) -> list[dict] | None:
    """One dict of the six pieces (ms) per joined write, once a run."""
    if "tx_roads" in obs.trace:
        return obs.trace["tx_roads"]
    obs.trace["tx_roads"] = None
    if not artifacts.program_keeps_records():
        return None
    run = artifacts.run_dir(obs)
    traces: dict[int, dict[str, dict]] = {}
    for d in os.listdir(run):
        m = re.fullmatch(r"node(\d+)", d)
        if not m:
            continue
        with open(artifacts.stop_dump(run, int(m.group(1)))) as f:
            dumped = json.load(f).get("tx_traces")
        if dumped is None:
            return None  # a program from before the traces
        traces[int(m.group(1))] = {t["hash"]: t for t in dumped}
    with open(os.path.join(run, "loadgen.out")) as f:
        lg = json.load(f)
    from tendermint_tpu.types.tx import tx_hash

    k0 = lg.get("lead_in_writes", lg.get("lead_in_operations", 0))
    kinds = lg.get("kind")
    roads = []
    for i in range(k0, len(lg["tx"])):
        if not lg["ok"][i] or (kinds is not None and kinds[i] != "update"):
            continue
        h = tx_hash(bytes.fromhex(lg["tx"][i])).hex().upper()
        a = traces.get(lg["node"][i], {}).get(h)
        p = next((t for t in (by.get(h) for by in traces.values())
                  if t is not None and "reap" in t["stages"]), None)
        if a is None or p is None:
            continue
        sa, sp = a["stages"], p["stages"]
        if any(k not in sa for k in ("rpc_ingress", "sig_gate",
                                     "block_commit", "rpc_reply")) \
                or "mempool_admit" not in sp:
            continue
        total = lg["done"][i] - lg["sent"][i]
        roads.append({k: 1000.0 * v for k, v in (
            ("edge", total - (sa["rpc_reply"] - sa["rpc_ingress"])),
            ("gate", sa["sig_gate"] - sa["rpc_ingress"]),
            ("to_proposer", sp["mempool_admit"] - sa["sig_gate"]),
            ("await_reap", sp["reap"] - sp["mempool_admit"]),
            ("reap_to_commit", sa["block_commit"] - sp["reap"]),
            ("commit_to_reply", sa["rpc_reply"] - sa["block_commit"]),
            ("total", total))})
    obs.trace["tx_roads"] = roads
    return roads


def read(obs, params, device):
    roads = _joined(obs)
    if roads is None or len(roads) < MIN_JOINED:
        return None
    return quantile([r[params["piece"]] for r in roads],
                    float(params["q"]) / 100.0)
