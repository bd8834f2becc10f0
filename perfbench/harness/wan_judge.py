"""The three comparisons of `committee-wan.steady` that only a net with
link delays can fail, as plain functions over what the nodes report:
`net` is a `reference/wan_ref.py` `WanNet`, `links` maps (from, to) to a
link's ping record and its delay line's counters (every node's
`net_info`), `per_node` is every node's height traces of the window.
No process, no RPC, nothing of the program: `scenarios/committee_wan.py`
feeds it on the chip, the tier-1 tests on a small in-process net."""

from __future__ import annotations

# the marks are time.time() of processes of one machine, rounded to a
# microsecond in the trace: the slack of the floor's comparison
STAMP_TOLERANCE_MS = 2.0
# a round trip is measured on one monotonic clock and rounded to a
# microsecond in the record
RTT_TOLERANCE_MS = 0.001


def links_without_sample(links: dict, n: int) -> list[tuple[int, int]]:
    return [(i, j) for i in range(n) for j in range(n) if i != j
            and not ((links.get((i, j)) or {}).get("rtt") or {}).get("count")]


def link_records(net, links: dict) -> tuple[list, list, list]:
    """(records for the readers, links without a sample, links whose
    smallest round trip is under the configured one)."""
    records, under = [], []
    missing = links_without_sample(links, net.n)
    for i, j, one_way, rtt_cfg in net.links():
        got = links.get((i, j)) or {}
        rtt = got.get("rtt") or {}
        rec = {"from": i, "to": j, "region_from": net.region_of(i),
               "region_to": net.region_of(j), "configured_rtt_ms": rtt_cfg,
               "rtt": rtt or None, "link": got.get("link")}
        if rtt.get("count"):
            # the smallest sample: a link's first ping falls before the
            # window and its next 40 s later, inside it or not, so the
            # last one reads the idle machine in one run and the loaded
            # one in the next
            rec["rtt_over_configured_ms"] = 1000.0 * rtt["min_s"] - rtt_cfg
            if 1000.0 * rtt["min_s"] + RTT_TOLERANCE_MS < rtt_cfg:
                under.append((i, j))
            # the delay the sender's line was given is the reference's too
            link = got.get("link") or {}
            if abs(1000.0 * float(link.get("delay_s", -1.0)) - one_way) > 1e-6:
                under.append((i, j))
        records.append(rec)
    return records, missing, sorted(set(under))


def heights(net, per_node: list[list[dict]],
            observer: int = 0) -> tuple[list[dict], int]:
    """For every height of the observer's traces: who proposed (the node
    whose trace of that height carries `propose_as_proposer`, the earliest
    if a later round gave a second), the observer's `precommit_quorum`
    less that instant, and the reference's floor. Returns the records and
    how many came in under the floor."""
    by_height: dict[int, list[tuple[float, int]]] = {}
    for i, traces in enumerate(per_node):
        for t in traces:
            at = (t.get("arrivals") or {}).get("propose_as_proposer")
            if at is not None:
                by_height.setdefault(t["height"], []).append((at, i))
    records, under = [], 0
    for t in per_node[observer]:
        quorum_at = (t.get("arrivals") or {}).get("precommit_quorum")
        proposers = sorted(by_height.get(t["height"], []))
        if quorum_at is None or not proposers:
            continue
        proposed_at, proposer = proposers[0]
        floor = net.quorum_floor_ms(proposer, observer)
        measured = 1000.0 * (quorum_at - proposed_at)
        if measured + STAMP_TOLERANCE_MS < floor:
            under += 1
        records.append({"height": t["height"], "proposer": proposer,
                        "measured_ms": measured, "floor_ms": floor,
                        "over_floor_ms": measured - floor})
    return records, under
