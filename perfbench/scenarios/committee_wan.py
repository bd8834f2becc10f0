"""Deployment `committee_wan`: `committee_net`'s committee with a constant
one-way delay on every link, set inside each node's own p2p stack
(`tendermint_tpu/p2p/delay_line.py`) through `config.toml` `[p2p]`:
node i is in region `regions[i mod len(regions)]`, every node gets the
same table of round-trip times (`rtt_ms`) and its own region, and each
end of a link delays what it sends by half the round trip between the
two regions. No relay, no extra process.

`committee_net.py` and `validator_net.py` are not this PR's to edit, so
`committee_net.run` is used whole with four names replaced for its
duration, the way it replaces `judge` itself:

- `procs.write_home`: each home's `[p2p]` section gets the two fields
  (the node's index is in `base.moniker`). A program without them (the
  parent commit) fails there, in the first seconds, with
  `config has no p2p.test_link_region`;
- `rpc.wait_heights`: after every node is at height 2, the window does
  not open until every directed link has a ping round trip on record (a
  link pings as it starts, so this costs no set-up time);
- `procs.free_ports`: the nodes' ports come from below every ephemeral
  range (see `ports_below_ephemeral`);
- `committee_net.judge_committee`: its two comparisons, and three more
  against the plain reference `reference/wan_ref.py`, limit 0 each:
  `links_without_rtt_sample`, `links_with_rtt_under_configured` (a
  link's MINIMUM ping round trip against its configured one, all
  n x (n-1) directed links, from every node's `net_info`), and
  `heights_under_quorum_floor` (node 0's `precommit_quorum` instant,
  which is its entry into `commit`, less the proposer's entry into
  `propose`, both wall-clock marks of one machine from the nodes' height
  traces, against `quorum_floor_ms(proposer, 0)`).

The comparing itself is `harness/wan_judge.py` (no process, no RPC: the
tier-1 tests run it on a small in-process net). What the judge read is
kept as `wan_links.json` in the run's directory for the per-layer
readers (`run_file_percentile`, `run_file_hist_percentile`).
"""

from __future__ import annotations

import json
import os
import re
import socket
import time

from harness import procs, rpc, wan_judge
from reference import wan_ref
from scenarios import committee_net as cn

LINKS_FILE = "wan_links.json"


# `--scale` key of the tests' control run alone, never in a configuration's
# file: {node index: {pair: ms}}, entries of `rtt_ms` that this one node's
# own table gets wrong. The node is a real one whose line delays its side of
# those links by the wrong amount; the judge keeps the configuration's table.
FAULT_KEY = "control_wrong_rtt_ms_of_node"


def link_table(cfg: dict, node: int | None = None) -> str:
    """`[p2p] test_link_rtt_ms` of the configuration's `rtt_ms`."""
    rtt = {**cfg["rtt_ms"], **(cfg.get(FAULT_KEY) or {}).get(str(node), {})}
    return ",".join(f"{pair}={float(ms):g}" for pair, ms in rtt.items())


def ports_below_ephemeral(n: int, first: int = 10000, last: int = 15999) -> list[int]:
    """n loopback ports that are free now, from below every ephemeral
    range (Linux 32768-60999; the chip machine's sandbox kernel
    16000-65535, with no split between bind and connect). `procs.free_ports`
    takes its ports FROM that range and the nodes bind them a minute later:
    the source port of a connection that a node which boots a moment
    earlier dials can then be the RPC port of one that boots later, which
    dies on `Address already in use` (PERF.md section 7, fault 9)."""
    ports = []
    for port in range(first, last + 1):
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        ports.append(port)
        if len(ports) == n:
            return ports
    raise procs.HarnessError(f"no {n} free ports in {first}-{last}")


def run(ctx) -> dict:
    cfg = ctx.config
    regions = cfg["regions"]
    real_write, real_wait, real_judge, real_ports = (
        procs.write_home, rpc.wait_heights, cn.judge_committee, procs.free_ports)
    extra: dict = {}

    def write_home(home, genesis, priv_validator, cfg_sets):
        i = int(re.fullmatch(r"node(\d+)", cfg_sets["base"]["moniker"]).group(1))
        sets = dict(cfg_sets)
        sets["p2p"] = {**sets.get("p2p", {}),
                       "test_link_region": regions[i % len(regions)],
                       "test_link_rtt_ms": link_table(cfg, i)}
        return real_write(home, genesis, priv_validator, sets)

    def wait_heights(addrs, h, deadline, alive=None):
        ok = real_wait(addrs, h, deadline, alive)
        if ok and h == 2:
            t0 = time.time()
            ok = wait_links(addrs, deadline, alive)
            extra["waited_for_rtt_samples_s"] = round(time.time() - t0, 3)
        return ok

    def judge_committee(ctx, cfg, addrs):
        two, notes = real_judge(ctx, cfg, addrs)
        three, more = judge_links(ctx, cfg, addrs)
        return two + three, {**notes, **more}

    procs.write_home, rpc.wait_heights = write_home, wait_heights
    cn.judge_committee = judge_committee
    # a run's ports start where its process id says, not its seed: the two
    # sides of a comparison share a seed and run in turn, and the second does
    # not meet the first on ports it has just closed
    procs.free_ports = lambda n: ports_below_ephemeral(
        n, first=10000 + (os.getpid() % 59) * 100)
    try:
        res = cn.run(ctx)
    finally:
        procs.write_home, rpc.wait_heights = real_write, real_wait
        cn.judge_committee, procs.free_ports = real_judge, real_ports
    res["notes"].update(extra)
    return res


# -- the links ------------------------------------------------------------------


def fleet_links(addrs) -> dict[tuple[int, int], dict]:
    """(from, to) -> {"rtt": record or None, "link": the delay line's
    counters or None}, from every node's `net_info`; node j is known by
    its moniker."""
    out = {}
    for i, a in enumerate(addrs):
        for p in rpc.call(a, "net_info", timeout=30)["peers"]:
            m = re.fullmatch(r"node(\d+)", (p.get("node_info") or {}).get("moniker", ""))
            if not m:
                continue
            st = p.get("connection_status") or {}
            out[(i, int(m.group(1)))] = {"rtt": st.get("rtt"), "link": st.get("link")}
    return out


def wait_links(addrs, deadline: float, alive=None) -> bool:
    while time.time() < deadline:
        if alive is not None:
            alive()
        try:
            if not wan_judge.links_without_sample(fleet_links(addrs), len(addrs)):
                return True
        except (OSError, rpc.RPCFailure):
            pass
        time.sleep(0.5)
    return False


def judge_links(ctx, cfg, addrs) -> tuple[list, dict]:
    net = wan_ref.WanNet(cfg["regions"], cfg["rtt_ms"], len(addrs))
    link_records, missing, under = wan_judge.link_records(net, fleet_links(addrs))
    lo, hi = cn.window_of(ctx.run_dir)
    per_node = [cn.in_window(rpc.call(a, "consensus_trace", {"last": 128},
                                      timeout=30)["traces"], lo, hi)
                for a in addrs]
    height_records, heights_under = wan_judge.heights(net, per_node)
    # every link says what the buckets of its lateness histogram are, and
    # one timer's code serves them all
    edges = {tuple((r["link"] or {}).get("late_edges_s") or ()) for r in link_records}
    with open(os.path.join(ctx.run_dir, LINKS_FILE), "w") as f:
        json.dump({"links": link_records, "heights": height_records,
                   "late_edges_s": list(edges.pop()) if len(edges) == 1 else []}, f)
    over = sorted(r["over_floor_ms"] for r in height_records)
    notes = {
        "links_judged": len(link_records),
        "links_without_rtt_sample": missing[:16],
        "links_with_rtt_under_configured": under[:16],
        "heights_judged_against_floor": len(height_records),
        "height_over_floor_ms": [round(x, 1) for x in over],
    }
    return [
        ("links_without_rtt_sample", len(missing), 0),
        ("links_with_rtt_under_configured", len(under), 0),
        ("heights_under_quorum_floor",
         heights_under if height_records else 1, 0),
    ], notes
