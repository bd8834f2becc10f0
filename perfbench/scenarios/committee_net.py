"""Deployment `committee_net`: `validator_net`'s launch and window at a
committee's size (the configuration's `validators`: tens of CLI node
processes, one daemon on the one chip, the generator a process of its
own), judged by `validator_net.judge`'s ten comparisons over ALL nodes
and two more that only a committee can fail:

- `commits_failing_plain_quorum`: every height of the window, its commit
  as node 0's `commit` RPC gives it, through `reference/commit_ref.py`
  (sign-bytes rebuilt and each precommit verified by plain Ed25519, more
  than two thirds of the power for that block id);
- `nodes_with_host_verified_sigs`: a node whose gateway verified a BATCH
  lane on the host inside the window (`device.verify_cpu_sigs` less
  `device.verify_single_sigs` of its heights' traces: a lone vote or a
  proposal is checked on the host by design, one signature at a time,
  and the program counts those apart), or whose receive routine never
  met the daemon (`aux.verify_ipc_s` 0 on every height: the node latched
  the host path at boot, ROADMAP Queue 2 A7). A program from before the
  counter (the parent commit) is judged by the second sign alone.

`validator_net.py` is not this PR's to edit, so its `run` is used whole
with two names replaced for its duration: `judge` (its ten comparisons
are computed by the original and the two are appended), and the wait for
height 2, which here ends after BOOT_LIMIT_S so that a program that
cannot carry the committee fails in seconds and not at the watchdog.
"""

from __future__ import annotations

import json
import os
import time

from harness import rpc
from reference import commit_ref
from scenarios import validator_net as vn

# every node at height 2 within this long of the last node's start. A
# committee that stands reaches it in 10-30 s; one that cannot elect a
# proposer in 150 s will not within the watchdog either
BOOT_LIMIT_S = 150.0


def run(ctx) -> dict:
    extra: dict = {}
    real_judge, real_wait = vn.judge, rpc.wait_heights

    def judge(ctx, cfg, mix, addrs, lg, idx, top, unanswered, status0, daemon):
        ten = real_judge(ctx, cfg, mix, addrs, lg, idx, top, unanswered,
                         status0, daemon)
        two, notes = judge_committee(ctx, cfg, addrs)
        extra.update(notes)
        return ten + two

    def wait_heights(addrs, h, deadline, alive=None):
        if h == 2:
            deadline = min(deadline, time.time() + BOOT_LIMIT_S)
        return real_wait(addrs, h, deadline, alive)

    vn.judge, rpc.wait_heights = judge, wait_heights
    try:
        res = vn.run(ctx)
    finally:
        vn.judge, rpc.wait_heights = real_judge, real_wait
    res["notes"].update(extra)
    return res


def window_of(run_dir: str) -> tuple[float, float]:
    with open(os.path.join(run_dir, "loadgen.window")) as f:
        win = json.load(f)
    return win["open_wall"], win["close_wall"]


def in_window(traces: list[dict], lo: float, hi: float) -> list[dict]:
    return [t for t in traces if lo <= t.get("started_at", 0) < hi]


def judge_committee(ctx, cfg, addrs) -> tuple[list, dict]:
    """The two comparisons (limit 0 each) and the notes beside them."""
    lo, hi = window_of(ctx.run_dir)
    per_node = [in_window(rpc.call(a, "consensus_trace", {"last": 128},
                                   timeout=30)["traces"], lo, hi)
                for a in addrs]
    # 11. every height of the window holds a commit that the plain
    #     reference counts as more than two thirds of the power
    chain_id = f"perfbench-{cfg['name']}"
    vals = commit_ref.validator_set(rpc.call(addrs[0], "genesis")["genesis"])
    heights = sorted(t["height"] for t in per_node[0])
    failing, lanes_refused = 0, 0
    for h in heights:
        block_id = rpc.call(addrs[0], "block", {"height": h})["block_meta"]["block_id"]
        commit = rpc.call(addrs[0], "commit", {"height": h})["commit"] or {}
        verdict = commit_ref.check_commit(chain_id, vals, h, block_id, commit)
        lanes_refused += len(verdict["refused"])
        if not verdict["quorum"]:
            failing += 1
    # 12. no node verified on the host behind the deployment's back
    on_host = []
    for i, heights_i in enumerate(per_node):
        cpu_sigs = sum(int(t["device"].get("verify_cpu_sigs", 0))
                       - int(t["device"]["verify_single_sigs"])
                       for t in heights_i
                       if "verify_single_sigs" in (t.get("device") or {}))
        met_daemon = any(float((t.get("aux") or {}).get("verify_ipc_s", 0)) > 0
                         for t in heights_i)
        if cpu_sigs > 0 or not met_daemon:
            on_host.append(i)
    unbatched = sum(1 for t in per_node[0]
                    if not (t.get("aux") or {}).get("vote_batches"))
    notes = {
        "commit_heights_checked": len(heights),
        "commit_lanes_refused_by_reference": lanes_refused,
        "nodes_on_host": on_host[:32],
        "node0_heights_without_batched_vote": unbatched,
    }
    return [
        ("commits_failing_plain_quorum",
         failing if heights else 1, 0),
        ("nodes_with_host_verified_sigs", len(on_host), 0),
    ], notes
