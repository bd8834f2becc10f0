"""`committee-wan.steady` (PR 32): its entries and data files held
against `committee.steady`'s (everything but the links is equal), the
table of round trips, the metrics it shares with `committee.steady`, the
three new readers on hand-made files whose answers are known, the three
new comparisons on a faulted input (a link with the delay off must read
over its limit), and the whole cell in rehearsal at 7 validators in 7
regions on the CPU daemon.

The rehearsals boot real node processes and a daemon that compiles its
kernels for the CPU backend on first use: 1-3 minutes a case. Run with
    python3 -m pytest perfbench/tests/test_wan_cell.py -q
"""

import importlib
import io
import itertools
import json
import os
from contextlib import redirect_stdout

import pytest
from conftest import BENCH, ROOT

from harness.observe import Observations
from reference import wan_ref
from harness import wan_judge
from scenarios import committee_wan

CELL = "committee-wan.steady"
WAN_KEYS = {"name", "deployment", "source", "why", "validators_published",
            "regions_published", "regions", "region_of_validator", "rtt_ms",
            "transport", "injected_message_delay_ms", "guarantees", "chip_mapping",
            "assumed", "reduced_note"}
NEW = ["height_over_floor_ms_p50", "link_rtt_over_configured_ms_p50",
       "link_delay_late_ms_p95", "vote_duplicates_per_accepted"]
SMALL = {"config": {"validators": 7,
                    "daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                       "TENDERMINT_DEVD_WARM": "",
                                       "TENDERMINT_TPU_COMB_MIN_SIGHT": "1"},
                               "warm_buckets": [8, 16, 32, 64], "warm_passes": 1}},
         "traffic": {"rate_per_s": 5, "signers": 6, "lead_in_s": 1.0,
                     "readback_sample": 12, "forged_writes": 4}}


def load(path):
    with open(path) as f:
        return json.load(f)


def read(metric, obs):
    spec = load(os.path.join(BENCH, "metrics", metric + ".json"))
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(obs, spec.get("params", {}), {})


# -- the entries ----------------------------------------------------------------


def test_the_cell_its_configuration_and_its_traffic():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert (cell[0]["config"], cell[0]["traffic"]) == ("committee-wan-signedkv",
                                                       "writes-committee")
    entry = [c for c in b["configs"] if c["name"] == "committee-wan-signedkv"][0]
    assert entry["reduced"] == ["validators"] and len(entry["source"]) <= 200
    for word in ("64 validators", "7 datacenters", "5 continents", "v0.11.0"):
        assert word in entry["source"], word
    for m in b["end_to_end"]:
        if m["name"].startswith("commit_latency"):
            assert m["workloads"][:3] == ["net4.steady", "committee.steady", CELL]


def test_the_configuration_is_the_committees_but_for_the_links():
    wan = load(os.path.join(BENCH, "configs", "committee-wan-signedkv.json"))
    com = load(os.path.join(BENCH, "configs", "committee-signedkv.json"))
    assert set(wan) - set(com) == {"regions_published", "regions",
                                   "region_of_validator", "rtt_ms"}
    assert set(com) <= set(wan)
    for key in com:
        if key not in WAN_KEYS:
            assert wan[key] == com[key], key       # app, consensus, base, ...
    assert wan["deployment"] == "committee_wan"
    assert wan["validators"] == com["validators"] == 16
    assert wan["validators_published"] == 64 and wan["reduced"] == ["validators"]
    assert "64 -> 16" in wan["reduced_note"]["validators"]
    assert wan["regions_published"] == "7 datacenters on 5 continents"
    assert wan["regions"] == ["us-east-1", "eu-central-1", "ap-northeast-1",
                              "us-west-1", "eu-west-1", "ap-southeast-2", "sa-east-1"]
    assert wan["injected_message_delay_ms"] == \
        "rtt_ms / 2 per link, inside each node's p2p stack"
    # the six guarantees word for word, and the seventh
    assert {k: v for k, v in wan["guarantees"].items() if k != "link_delay"} == \
        com["guarantees"]
    assert list(wan["guarantees"])[-1] == "link_delay"
    # every set value is owned up to
    assert set(com["assumed"]) <= set(wan["assumed"])
    for key in ("rtt_ms", "no_jitter_no_loss", "clients_local", "full_mesh"):
        assert key in wan["assumed"], key
    assert "memory" in wan["assumed"]["rtt_ms"]
    # 3, 3, 2, 2, 2, 2, 2 validators a region
    per_region = [sum(1 for i in range(16) if i % 7 == r) for r in range(7)]
    assert per_region == [3, 3, 2, 2, 2, 2, 2]


def test_the_table_is_symmetric_complete_and_nearly_metric():
    wan = load(os.path.join(BENCH, "configs", "committee-wan-signedkv.json"))
    regions, rtt = wan["regions"], wan["rtt_ms"]
    assert len(rtt) == 28                          # 21 pairs and the diagonal
    pairs = {frozenset(k.split(":")) for k in rtt}
    assert len(pairs) == 28                        # each unordered pair once
    net = wan_ref.WanNet(regions, rtt, 16)         # raises if one is missing
    for a in regions:
        assert net.rtt[(a, a)] == 1.0
    for a, b in itertools.permutations(regions, 2):
        assert net.rtt[(a, b)] == net.rtt[(b, a)] >= 25.0
    worst = max(net.rtt[(a, b)] - net.rtt[(a, k)] - net.rtt[(k, b)]
                for a, b, k in itertools.permutations(regions, 3))
    assert worst <= 15.0
    # the issue's numbers, the extremes of them
    assert net.rtt[("us-east-1", "us-west-1")] == 62.0
    assert net.rtt[("eu-west-1", "eu-central-1")] == 25.0
    assert max(rtt.values()) == net.rtt[("sa-east-1", "ap-southeast-2")] == 312.0
    # what the scenario writes into [p2p] parses to the same table in the program
    from tendermint_tpu.p2p.delay_line import parse_rtt_table

    parsed = parse_rtt_table(committee_wan.link_table(wan), must_hold="sa-east-1")
    assert parsed == net.rtt


def test_every_wan_metric_has_its_entry_its_file_and_its_twin():
    import run as bench_run

    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m for m in b["per_layer"] if CELL in m["workloads"]]
    committee = [m for m in b["per_layer"] if "committee.steady" in m["workloads"]]
    # every metric of `committee.steady` is read here too, by the same
    # entry (its twin, with its reader and parameters), and four of its own
    assert [m for m in mine if m["name"] not in NEW] == committee
    assert {m["name"] for m in mine} - {m["name"] for m in committee} == set(NEW)
    fold = load(os.path.join(BENCH, "tests", "data", "per_layer_fold.json"))
    before = {r["new"] for r in fold["entries"] if r["cell"] == CELL}
    assert len(before) == 33 and before <= {m["name"] for m in mine}
    for m in mine:
        assert m["moves"] in ("commit_latency_p50_ms", "commit_latency_p95_ms")
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert {k: spec[k] for k in m} == m
        reader, _params = bench_run.metric_reader(spec, CELL)
        assert hasattr(importlib.import_module("readers." + reader), "read")
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            continue
        assert bench_run.metric_reader(spec, "committee.steady") == \
            bench_run.metric_reader(spec, CELL)
    assert {m["layer"] for m in mine if m["name"] in NEW} == \
        {"consensus", "p2p links", "vote plane"}
    for name in ("verify_kernel_roofline_share", "device_idle_share"):
        assert name in {m["name"] for m in mine}


# -- the new readers -------------------------------------------------------------

OPEN = 1_000_000.0
EDGES = [0.0001, 0.001, 0.01]


def make_run(tmp_path, links=None, heights=None, counters=None):
    run = tmp_path / "run"
    (run / "trace").mkdir(parents=True)
    if links is not None or heights is not None:
        with open(run / committee_wan.LINKS_FILE, "w") as f:
            json.dump({"links": links or [], "heights": heights or [],
                       "late_edges_s": EDGES}, f)
    for i, c in enumerate(counters or []):
        d = run / f"node{i}" / "flightrec"
        d.mkdir(parents=True)
        with open(d / "dump-20261003T000000-stop.json", "w") as f:
            json.dump({"counters": c, "consensus_traces": []}, f)
    o = Observations(window_s=10.0, open_wall=OPEN)
    o.trace = {"dir": str(run / "trace")}
    return o


def test_new_readers_on_files_whose_answers_are_known(tmp_path):
    links = [
        {"rtt_over_configured_ms": 1.0,
         "link": {"late_hist": [10, 0, 0, 0], "late_max_s": 0.0001}},
        {"rtt_over_configured_ms": 3.0,
         "link": {"late_hist": [0, 8, 0, 0], "late_max_s": 0.001}},
        {"rtt_over_configured_ms": 8.0,
         "link": {"late_hist": [0, 0, 1, 1], "late_max_s": 0.05}},
        {"rtt": None, "link": None},               # a link with no sample
    ]
    heights = [{"over_floor_ms": x} for x in (100.0, 300.0, 200.0)]
    counters = [{"vote_duplicates": 10, "vote_accepted": 100},
                {"vote_duplicates": 30, "vote_accepted": 100}]
    obs = make_run(tmp_path, links, heights, counters)
    assert read("height_over_floor_ms_p50", obs) == pytest.approx(200.0)
    assert read("link_rtt_over_configured_ms_p50", obs) == pytest.approx(3.0)
    # 20 frames: ten under 0.1 ms, eight in 0.1-1 ms, one in 1-10 ms, one above
    # 10 ms. The 19th of them (rank 19.0) closes the 1-10 ms bucket
    assert read("link_delay_late_ms_p95", obs) == pytest.approx(10.0)
    assert read("vote_duplicates_per_accepted", obs) == pytest.approx(0.2)
    # the open bucket is bounded by the largest value any link saw
    spec = load(os.path.join(BENCH, "metrics", "link_delay_late_ms_p95.json"))
    reader = importlib.import_module("readers." + spec["reader"])
    assert reader.read(obs, {**spec["params"], "q": 100}, {}) == pytest.approx(50.0)
    assert reader.read(obs, {**spec["params"], "q": 25}, {}) == pytest.approx(0.05)


def test_new_readers_read_nothing_from_a_run_the_judge_left_nothing_of(tmp_path):
    """The parent commit: no file from the judge, dumps without the counters."""
    obs = make_run(tmp_path, counters=[{"height": 3}])
    for metric in NEW:
        assert read(metric, obs) is None, metric
    obs = make_run(tmp_path / "empty", links=[], heights=[])
    for metric in NEW[:3]:
        assert read(metric, obs) is None, metric


def test_frames_per_wake_sums_its_two_numerators_over_the_fleet(tmp_path):
    """`p2p_io_frames_per_wake` (every cell): the fleet's frames read and
    written over its I/O loops' wake-ups."""
    counters = [{"p2p_io_frames_in": 30, "p2p_io_frames_out": 20, "p2p_io_wakes": 10},
                {"p2p_io_frames_in": 10, "p2p_io_frames_out": 0, "p2p_io_wakes": 10}]
    obs = make_run(tmp_path, counters=counters)
    assert read("p2p_io_frames_per_wake", obs) == pytest.approx(60 / 20)
    # a program from before the loop's counters
    obs = make_run(tmp_path / "old", counters=[{"p2p_io_wakes": 3}])
    assert read("p2p_io_frames_per_wake", obs) is None


# -- the new comparisons on a faulted input ---------------------------------------


def fleet(net, late=0.0003):
    """What every node's `net_info` would say of a net that keeps its delays."""
    return {(i, j): {"rtt": {"count": 2, "min_s": rtt / 1000 + late,
                             "last_s": rtt / 1000 + 2 * late, "smoothed_s": 0.0},
                     "link": {"delay_s": one_way / 1000, "region": net.region_of(j)}}
            for i, j, one_way, rtt in net.links()}


def test_the_link_comparisons_on_a_sound_and_on_a_faulted_net():
    wan = load(os.path.join(BENCH, "configs", "committee-wan-signedkv.json"))
    net = wan_ref.WanNet(wan["regions"], wan["rtt_ms"], 16)
    records, missing, under = wan_judge.link_records(net, fleet(net))
    assert len(records) == 240 and missing == [] and under == []
    # the smallest sample, not the last: one number a link whatever the
    # instant of its newest ping
    assert all(r["rtt_over_configured_ms"] == pytest.approx(0.3) for r in records)
    # node 3's delay line off towards node 9: that link's pings come back in
    # half the time, and the line says 0
    faulted = fleet(net)
    one_way = net.link_one_way_ms(3, 9)
    faulted[(3, 9)]["link"]["delay_s"] = 0.0
    faulted[(3, 9)]["rtt"]["min_s"] = one_way / 1000 + 0.0003
    # node 12 never heard a pong from node 0, and node 7 has no such peer
    faulted[(12, 0)]["rtt"] = {"count": 0, "min_s": 0.0, "last_s": 0.0}
    del faulted[(7, 8)]
    _records, missing, under = wan_judge.link_records(net, faulted)
    assert missing == [(7, 8), (12, 0)] and under == [(3, 9)]
    # a sample one microsecond under is rounding, a tenth of a millisecond is not
    edge = fleet(net)
    edge[(0, 1)]["rtt"]["min_s"] = net.link_rtt_ms(0, 1) / 1000 - 0.0000005
    edge[(0, 2)]["rtt"]["min_s"] = net.link_rtt_ms(0, 2) / 1000 - 0.0001
    assert wan_judge.link_records(net, edge)[2] == [(0, 2)]


def test_the_floor_comparison_on_a_sound_and_on_a_faulted_net():
    wan = load(os.path.join(BENCH, "configs", "committee-wan-signedkv.json"))
    net = wan_ref.WanNet(wan["regions"], wan["rtt_ms"], 16)

    def trace(height, **arrivals):
        return {"height": height, "arrivals": arrivals}

    per_node = [[] for _ in range(16)]
    t0 = OPEN
    for h, proposer in ((5, 4), (6, 5), (7, 0)):
        floor = net.quorum_floor_ms(proposer, 0) / 1000
        took = floor + 0.120 if h != 6 else floor - 0.010   # height 6: too soon
        if proposer == 0:
            per_node[0].append(trace(h, propose_as_proposer=t0, precommit_quorum=t0 + took))
        else:
            per_node[proposer].append(trace(h, propose_as_proposer=t0))
            per_node[0].append(trace(h, precommit_quorum=t0 + took))
        t0 += 2.0
    per_node[0].append(trace(8, precommit_quorum=t0))        # its proposer's trace is lost
    records, under = wan_judge.heights(net, per_node)
    assert [r["height"] for r in records] == [5, 6, 7] and under == 1
    assert [r["proposer"] for r in records] == [4, 5, 0]
    assert records[0]["over_floor_ms"] == pytest.approx(120.0, abs=0.01)
    assert records[1]["over_floor_ms"] == pytest.approx(-10.0, abs=0.01)
    # inside the stamps' tolerance is not under
    per_node[0][1]["arrivals"]["precommit_quorum"] += 0.009
    assert wan_judge.heights(net, per_node)[1] == 0
    # a second round's proposer does not move the instant the floor counts from
    per_node[9].append(trace(5, propose_as_proposer=OPEN + 3.0))
    assert wan_judge.heights(net, per_node)[0][0]["proposer"] == 4


# -- the whole cell, in rehearsal -------------------------------------------------


def last_run_dir():
    from harness import procs

    return os.path.join(procs.RUN_ROOT, CELL)


def run_cell(scale, seconds, trace=0):
    import run as bench_run

    argv = ["--workload", CELL, "--seed", str(2**31 + 131), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearsal",
            "--scale", json.dumps(scale)]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    return line, over


def test_seven_validators_in_seven_regions_are_correct_by_all_fifteen_comparisons():
    line, over = run_cell(SMALL, 10, trace=1)
    assert line["correct"] is True and not over
    assert len(line["compared"]) == 15
    assert list(line["compared"])[-3:] == ["links_without_rtt_sample",
                                           "links_with_rtt_under_configured",
                                           "heights_under_quorum_floor"]
    assert all(c["limit"] == 0 for c in line["compared"].values())
    assert line["attempted"] == 50 and line["failed"] == 0
    notes = line["notes"]
    assert notes["links_judged"] == 42 and notes["heights_judged_against_floor"] >= 3
    assert min(notes["height_over_floor_ms"]) >= -wan_judge.STAMP_TOLERANCE_MS
    assert notes["waited_for_rtt_samples_s"] < 5.0
    for metric in NEW + ["height_votes_ms_p50", "fleet_cpu_share",
                         "rounds_over_zero"]:
        assert metric in line["metrics"], metric
    assert line["metrics"]["rounds_over_zero"]["value"] == 0
    assert line["metrics"]["link_rtt_over_configured_ms_p50"]["value"] > 0
    assert line["metrics"]["height_over_floor_ms_p50"]["value"] > 0
    # a vote round waits for the net: the links' legs are in the height
    assert line["metrics"]["height_interval_ms_mean"]["value"] > 1150.0


def test_a_real_node_whose_line_delays_too_little_reads_over_its_limit():
    """The control is a node, not a patched reply: node 1 (eu-central-1)
    is given a table in which its round trip to us-east-1 is 2 ms where
    the configuration says 90, so its line lets frames to nodes 0 (the
    only other node of that region among 7) out 44 ms early. Each end of
    the link measures it with its own ping: out in 1 ms and back in 45, or
    the reverse, 46 where no round trip may be under 90. Every other
    comparison holds; the floor's too, which on this host cannot fail
    (the host's work of a height is longer than any floor: PERF.md)."""
    wan = load(os.path.join(BENCH, "configs", "committee-wan-signedkv.json"))
    assert wan["rtt_ms"]["us-east-1:eu-central-1"] == 90
    assert committee_wan.FAULT_KEY not in wan
    scale = {**SMALL, "config": {**SMALL["config"], committee_wan.FAULT_KEY: {
        "1": {"us-east-1:eu-central-1": 2}}}}
    line, over = run_cell(scale, 6)
    assert line["correct"] is False
    assert over == {"links_with_rtt_under_configured"}
    assert line["compared"]["links_with_rtt_under_configured"]["value"] == 2
    assert line["notes"]["links_with_rtt_under_configured"] == [[0, 1], [1, 0]]
    assert line["compared"]["heights_under_quorum_floor"]["value"] == 0
    links = {(r["from"], r["to"]): r
             for r in load(os.path.join(last_run_dir(), committee_wan.LINKS_FILE))["links"]}
    for pair in ((0, 1), (1, 0)):
        # the ping alone says so, whatever the node reports of its line
        assert 45.0 <= 1000 * links[pair]["rtt"]["min_s"] < 90.0
    assert links[(0, 1)]["link"]["delay_s"] == 0.045      # node 0 kept its side
    assert links[(1, 0)]["link"]["delay_s"] == 0.001      # node 1 did not
    assert 1000 * links[(1, 2)]["rtt"]["min_s"] >= 225.0  # its other links hold


def test_the_control_key_changes_one_nodes_table_and_nothing_else():
    wan = load(os.path.join(BENCH, "configs", "committee-wan-signedkv.json"))
    plain = committee_wan.link_table(wan)
    assert plain == committee_wan.link_table(wan, 1) and "us-east-1:eu-central-1=90" in plain
    wrong = {**wan, committee_wan.FAULT_KEY: {"1": {"us-east-1:eu-central-1": 2}}}
    assert committee_wan.link_table(wrong, 0) == plain
    assert committee_wan.link_table(wrong, 1) == plain.replace(
        "us-east-1:eu-central-1=90", "us-east-1:eu-central-1=2")


def test_the_nodes_ports_come_from_below_every_ephemeral_range():
    import socket

    ports = committee_wan.ports_below_ephemeral(32)
    assert len(set(ports)) == 32 and all(10000 <= p < 16000 for p in ports)
    # a port somebody holds is passed over
    with socket.socket() as held:
        held.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)   # as a node's listener
        held.bind(("127.0.0.1", ports[0]))
        held.listen(1)
        assert ports[0] not in committee_wan.ports_below_ephemeral(32)
    with pytest.raises(Exception, match="free ports"):
        committee_wan.ports_below_ephemeral(8, first=15000, last=15003)


def test_a_program_without_the_fields_fails_in_write_home(tmp_path):
    """What the parent commit meets: its `[p2p]` has no such field, so the
    home is refused before any process starts (the whole run then ends in
    its first seconds with exit code 1 and no result line: tried on the
    parent's checkout, PERF.md)."""
    from harness import procs

    with pytest.raises(procs.HarnessError, match="config has no p2p.no_such_field"):
        procs.write_home(str(tmp_path / "home"), None, None,
                         {"p2p": {"no_such_field": "x"}})
    # and this program has them
    from tendermint_tpu.config.config import P2PConfig

    assert P2PConfig().test_link_region == "" and P2PConfig().test_link_rtt_ms == ""
