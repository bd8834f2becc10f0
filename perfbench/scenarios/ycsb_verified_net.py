"""Deployment `ycsb_verified_net`: `ycsb_net` whole (its load, its pool's
history, its window, its judge and every comparison of it) with the
proofs of the reads made part of the result, the guarantee
`verified_read`: a read's proof verifies against the app hash of the
committed header at the height after the one the read names, as a light
client's `verified_query` checks it.

- the generator (`harness/ycsb_proof_loadgen.py`, `ycsb_loadgen.py`'s
  operations and timing) keeps the value and the proof that a seeded
  sample of the window's reads (`proof_sample` of the traffic) were
  answered with, and sends each port's operations from a process of its
  own;
- after the window, for every answered read of the sample, the judge
  fetches the header at (named height + 1) from the node that answered
  (`commit` RPC) and holds the proof, the key and the value to its app
  hash through `reference/statetree_ref.py`, and the value to the
  digest the generator recorded (which `ycsb_net`'s judge holds to
  `reference/ycsb_ref.py`).

`ycsb_net.py` is not this deployment's to edit, so its `run` is used
whole with two names replaced for its duration, as `committee_net`
does with `validator_net`: `subprocess` as `ycsb_net` sees it (the one
process it starts with Popen, the generator, is started as this
deployment's, with the traffic's `proof_sample` added to its
parameters), and `judge_live` (the original's comparisons, then
these two).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import time

from harness import procs, rpc
from reference import statetree_ref, ycsb_ref
from scenarios import ycsb_net

HEADER_WAIT_S = 60.0


def run(ctx) -> dict:
    real_judge, real_subprocess = ycsb_net.judge_live, ycsb_net.subprocess

    def judge_live(ctx, cfg, mix, addrs, lg, *rest):
        comparisons, notes = real_judge(ctx, cfg, mix, addrs, lg, *rest)
        more, proof_notes = judge_proofs(mix, addrs, lg)
        notes.update(proof_notes)
        return comparisons + more, notes

    ycsb_net.judge_live = judge_live
    ycsb_net.subprocess = _Launch(ctx.traffic)
    try:
        return ycsb_net.run(ctx)
    finally:
        ycsb_net.judge_live, ycsb_net.subprocess = real_judge, real_subprocess


class _Launch:
    """`subprocess` as `ycsb_net` sees it: its one Popen, of
    `ycsb_loadgen.py`, starts `ycsb_proof_loadgen.py` on the same
    parameters and the traffic's `proof_sample`."""

    STDOUT, TimeoutExpired = subprocess.STDOUT, subprocess.TimeoutExpired

    def __init__(self, mix: dict):
        self.mix = mix

    def Popen(self, argv, **kwargs):  # noqa: N802 — subprocess's name
        python, script, params = argv
        if os.path.basename(script) != "ycsb_loadgen.py":
            raise procs.HarnessError(f"unexpected process {script}")
        with open(params) as f:
            p = json.load(f)
        p["proof_sample"] = int(self.mix["proof_sample"])
        with open(params, "w") as f:
            json.dump(p, f)
        return subprocess.Popen(
            [python, os.path.join(procs.HERE, "ycsb_proof_loadgen.py"), params],
            **kwargs)


def judge_proofs(mix: dict, addrs, lg: dict) -> tuple[list, dict]:
    """Every answered read of the generator's sample, its proof against
    the app hash of header (named height + 1) on the node that answered
    it (limit 0), and at least `proofs_checked_min` of them checked."""
    sample = [i for i in lg["proof_sample"] if lg["ok"][i]]
    top = max((lg["height"][i] for i in sample), default=0)
    if not rpc.wait_heights(addrs, top + 1, time.time() + HEADER_WAIT_S):
        raise procs.HarnessError(f"not every node reached height {top + 1}")
    app_hash: dict[tuple[int, int], bytes] = {}
    failing = 0
    for i in sample:
        node, height = lg["node"][i], lg["height"][i] + 1
        if (node, height) not in app_hash:
            header = rpc.call(addrs[node], "commit", {"height": height},
                              timeout=30)["header"]
            app_hash[node, height] = bytes.fromhex(header["app_hash"])
        value_hex, proof_hex = lg["proofs"].get(str(i), ("", ""))
        value = bytes.fromhex(value_hex)
        length, digest = lg["got"][i]
        if (len(value), hashlib.sha256(value).hexdigest()) != (length, digest) \
                or not statetree_ref.proves(proof_hex, ycsb_ref.key_of(
                    lg["record"][i]), value, app_hash[node, height]):
            failing += 1
    short = max(0, int(mix["proofs_checked_min"]) - len(sample))
    return [
        ("proofs_not_verifying", failing, 0),
        ("proofs_checked_under_minimum", short, 0),
    ], {"proofs_checked": len(sample),
        "proof_headers_fetched": len(app_hash)}
