"""`ycsb-a.steady` (PR 35): its entries and data files, the generator
against `reference/ycsb_ref.py`, the pool's plain model against a
hand-made log, the pool's cost functions, the whole cell in rehearsal at a
small size on the CPU daemon, and the faults its own comparisons catch (a
pool that leaves its slots stale is in tests/test_comb_open_pool.py: it
needs JAX in the test's process, and a harness process may not have it).

The rehearsals boot real node processes and a daemon that compiles its
kernels for the CPU backend on first use: 2-4 minutes a case. Run with
    python3 -m pytest perfbench/tests/test_ycsb_cell.py -q
"""

import io
import json
import os
from contextlib import redirect_stdout

import pytest
from conftest import BENCH, ROOT

CELL = "ycsb-a.steady"
SMALL = {"config": {"recordcount": 1024, "load": {"txs_per_block": 64},
                    "pool_history": {"lanes_a_batch": 16},
                    "daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                       "TENDERMINT_DEVD_WARM": "",
                                       "TENDERMINT_TPU_COMB_MIN_SIGHT": "2",
                                       "TENDERMINT_TPU_COMB_CAP": "32",
                                       "TENDERMINT_TPU_COMB_OPEN": "1"},
                               "warm_buckets": [8, 16, 32, 64], "warm_passes": 2}},
         "traffic": {"rate_per_s": 20, "readback_sample": 20}}
POOL_METRICS = [
    "pool_miss_lane_share", "table_builds_per_s", "pool_evictions_per_s",
    "pool_resident_share", "table_build_ms_p50", "pool_update_ms_p50",
    "read_latency_ms_p95", "build_kernel_roofline_share",
    "update_kernel_roofline_share", "ladder_kernel_roofline_share"]
# read by this cell and by `burst.drain` alone
APPLY_METRICS = ["apply_verify_ms_p50", "apply_app_ms_p50", "block_parts_ms_p50"]
# read by a reader of this cell's own
OWN_READER = {"device_idle_share", "verify_kernel_sigs_per_s",
              "verify_kernel_roofline_share"}


def load(path):
    with open(path) as f:
        return json.load(f)


# -- the entries ----------------------------------------------------------------


def test_the_cell_its_configuration_and_its_traffic():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert (cell[0]["config"], cell[0]["traffic"]) == ("ycsb-a-signedkv", "ycsb-a")
    assert b["workloads"].index(cell[0]) == 1 + [
        w["name"] for w in b["workloads"]].index("committee-wan.steady")
    entry = [c for c in b["configs"] if c["name"] == "ycsb-a-signedkv"][0]
    assert entry["reduced"] == ["recordcount"] and len(entry["source"]) <= 200
    cfg = load(os.path.join(ROOT, entry["file"]))
    net4 = load(os.path.join(BENCH, "configs", "net4-signedkv.json"))
    # net4-signedkv key for key outside the keys ISSUE 35 names
    for key in ("validators", "app", "transport", "injected_message_delay_ms",
                "consensus", "base", "node_env", "chips"):
        assert cfg[key] == net4[key], key
    assert cfg["daemon"]["warm_buckets"] == net4["daemon"]["warm_buckets"]
    env, env4 = cfg["daemon"]["env"], net4["daemon"]["env"]
    assert {k: v for k, v in env.items() if k in env4
            and k != "TENDERMINT_TPU_COMB_MIN_SIGHT"} == {
        k: v for k, v in env4.items() if k != "TENDERMINT_TPU_COMB_MIN_SIGHT"}
    assert set(env) - set(env4) == {"TENDERMINT_TPU_COMB_CAP",
                                    "TENDERMINT_TPU_COMB_OPEN"}
    assert env["TENDERMINT_TPU_COMB_CAP"] == "12288"
    assert env["TENDERMINT_TPU_COMB_MIN_SIGHT"] in ("1", "2")
    assert f"TENDERMINT_TPU_COMB_MIN_SIGHT={env['TENDERMINT_TPU_COMB_MIN_SIGHT']}" \
        in cfg["assumed"]
    for name, text in net4["guarantees"].items():
        assert cfg["guarantees"][name] == text
    assert set(cfg["guarantees"]) - set(net4["guarantees"]) == {"fresh_read"}
    for name in ("TENDERMINT_DEVD_KERNEL=comb", "TENDERMINT_DEVD_WARM=''",
                 "TENDERMINT_TPU_MIN_BATCH=1"):
        assert cfg["assumed"][name] == net4["assumed"][name]
    for name in ("record_owner", "one_value_a_record", "zipfian"):
        assert name in cfg["assumed"]
    assert cfg["deployment"] == "ycsb_net" and cfg["reduced"] == ["recordcount"]
    assert cfg["recordcount"] == 65536
    assert cfg["recordcount"] > 2 * int(env["TENDERMINT_TPU_COMB_CAP"])
    assert (cfg["fieldcount"], cfg["fieldlength"]) == (10, 100)
    mix = load(os.path.join(BENCH, "traffic", "ycsb-a.json"))
    steady = load(os.path.join(BENCH, "traffic", "writes-steady.json"))
    for key in ("arrivals", "targets", "lead_in_s", "request_timeout_s",
                "readback_sample", "trace_window_s"):
        assert mix[key] == steady[key], key
    assert (mix["read_share"], mix["update_share"]) == (0.5, 0.5)
    assert mix["zipfian_constant"] == 0.99 and mix["forged_writes"] == 12
    assert mix["rate_per_s"] == 0.8 * mix["sweep"]["knee_ops_per_s"]
    for m in b["end_to_end"]:
        if m["name"].startswith("commit_latency"):
            assert CELL in m["workloads"]
    [read] = [m for m in b["end_to_end"] if m["name"] == "read_latency_p50_ms"]
    assert read["workloads"] == [CELL] and read["unit"] == "ms"
    assert (read["better"], read["source"]) == ("lower", "host_clock")


def test_every_ycsb_metric_has_its_entry_its_file_and_its_reader():
    import importlib

    import run as bench_run

    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"]: m for m in b["per_layer"] if CELL in m["workloads"]}
    net4 = {m["name"] for m in b["per_layer"] if "net4.steady" in m["workloads"]}
    # the pool's metrics, every metric of `net4.steady` (its twin), and the
    # apply's stamps and the block's parts; the reads' median is end to end
    assert set(mine) == set(POOL_METRICS) | net4 | set(APPLY_METRICS)
    fold = load(os.path.join(BENCH, "tests", "data", "per_layer_fold.json"))
    before = {r["new"]: r["as"] for r in fold["entries"] if r["cell"] == CELL}
    assert len(before) == 38 and before.pop("read_latency_p50_ms") == "end_to_end"
    assert set(before) <= set(mine)
    for name, m in mine.items():
        spec = load(os.path.join(BENCH, "metrics", name + ".json"))
        assert {k: spec[k] for k in m} == m
        reader, _params = bench_run.metric_reader(spec, CELL)
        assert hasattr(importlib.import_module("readers." + reader), "read")
        if name in net4:
            own = bench_run.metric_reader(spec, "net4.steady") != (reader, _params)
            assert own == (name in OWN_READER), name
        if "roofline" in name:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for name in APPLY_METRICS:
        assert mine[name]["workloads"] == [CELL, "burst.drain"]


# -- the generator, the references, the costs ---------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 99])
def test_the_generator_draws_what_the_reference_draws(seed):
    from harness import ycsb
    from reference import ed25519_ref, ycsb_ref

    ops = ycsb.draw_operations(seed, 600, 4096, 0.5, 0.99)
    assert ops == ycsb_ref.operations(seed, 600, 4096, 0.5, 0.99)
    reads = sum(1 for k, _r in ops if k == "read")
    assert 240 < reads < 360
    top = max(set(r for _k, r in ops), key=[r for _k, r in ops].count)
    assert [r for _k, r in ops].count(top) > 30          # a hot record
    for r in (0, 5, top):
        assert ycsb.record_key(r) == ycsb_ref.key_of(r)
        assert ycsb.record_value(seed, r, 3) == ycsb_ref.value_of(seed, r, 3)
        assert len(ycsb_ref.value_of(seed, r, 0)) == 1000
    pub, sign = ycsb.make_keypair()(ycsb.record_secret(seed, 5))
    assert pub == ycsb_ref.owner_key(seed, 5)
    assert ed25519_ref.verify(pub, b"m", sign(b"m"))
    assert ycsb.fnv1a64(0) == 0xA8C7F832281A39C5       # FNV-1a of eight zero octets


def test_the_values_a_read_may_return():
    from reference import ycsb_ref

    store = ycsb_ref.Store(seed=1, recordcount=64)
    store.acknowledge(9, version=11, height=5, position=2)
    store.acknowledge(9, version=4, height=5, position=0)
    store.acknowledge(9, version=20, height=7, position=0)
    hist = store.history(9)
    assert [v for _h, _p, v in hist] == [0, 4, 11, 20]
    assert store.at_height(9, 4) == store.value(9, 0)
    assert store.at_height(9, 6) == store.value(9, 11)
    assert store.final(9) == store.value(9, 20)
    # version -> (node that acknowledged, sent, acknowledged)
    writes = {4: (0, 1.0, 2.0), 11: (1, 1.1, 2.0), 20: (0, 3.0, 4.0)}
    may = ycsb_ref.versions_a_read_may_return
    # node 0 acknowledged version 4 before the read: nothing older than 4;
    # 11 follows it in the chain; 20 was not yet sent when the read ended
    assert may(hist, writes, 0, 2.5, 2.6) == {4, 11}
    # node 1 acknowledged 11: the floor is 11 itself
    assert may(hist, writes, 1, 2.5, 2.6) == {11}
    # node 2 acknowledged nothing: it may still hold the loaded value
    assert may(hist, writes, 2, 2.5, 3.5) == {0, 4, 11, 20}
    assert may(hist, writes, 0, 4.5, 4.6) == {20}


def test_what_an_updates_answer_says():
    from scenarios.ycsb_net import answer_kind

    def answer(code, log, deliver=None):
        return json.dumps({"check_tx": {"code": code, "log": log},
                           "deliver_tx": {"code": deliver, "log": ""}})

    assert answer_kind(True, "") == "acked"
    # turned away under load: a failed operation, not a verdict
    assert answer_kind(False, answer(6, "mempool_shed_writes:default")) == "shed"
    assert answer_kind(False, answer(6, "mempool_lane_full:default")) == "shed"
    assert answer_kind(False, answer(3, "signature gate saturated; retry")) == "shed"
    # a verdict on the write itself
    assert answer_kind(False, answer(3, "invalid signature (batch pre-verify)")) \
        == "refused"
    assert answer_kind(False, answer(3, "invalid signature")) == "refused"
    assert answer_kind(False, answer(6, "invalid signature")) == "refused"
    assert answer_kind(False, answer(0, "", deliver=3)) == "refused"
    # no verdict at all: the chain says what became of the write
    assert answer_kind(False, "timed out waiting for CheckTx") == "open"
    assert answer_kind(False, "shed:inflight_cap") == "open"
    assert answer_kind(False, "TimeoutError: ") == "open"
    assert answer_kind(False, None) == "open"


def test_the_pool_model_on_a_hand_made_log():
    from reference import pool_lru_ref

    header = {"routes": list(pool_lru_ref.ROUTES), "usable_slots": 2,
              "min_sight": 2}
    log = [
        {"k": [1, 2], "r": "22", "e": []},        # first sights: the ladder
        {"k": [1, 2, 1], "r": "333", "e": []},    # second: built; 1 used last
        {"k": [3], "r": "2", "e": []},
        {"k": [3, 1], "r": "31", "e": [2]},       # 3 takes the slot of 2
        {"k": [2, 0], "r": "40", "e": [3]},       # 2 returns: rebuilt; a lane
    ]                                             # with no key
    out = pool_lru_ref.replay(header, log)
    assert out["lanes_routed_unlike_reference"] == 0
    assert out["counts"] == {"malformed": 1, "hit": 1, "first_sight": 3,
                             "built": 4, "rebuilt": 1, "undecodable": 0}
    assert (out["evictions"], out["resident"]) == (2, 2)
    # a program that served key 2 from a slot it no longer holds
    stale = [dict(b) for b in log]
    stale[4] = {"k": [2, 0], "r": "10", "e": []}
    assert pool_lru_ref.replay(header, stale)["lanes_routed_unlike_reference"] == 2
    # a program that evicted the wrong key
    wrong = [dict(b) for b in log]
    wrong[3] = {"k": [3, 1], "r": "31", "e": [1]}
    assert pool_lru_ref.replay(header, wrong)["lanes_routed_unlike_reference"] >= 1


def test_the_miss_programs_costs_and_that_no_share_can_pass_100():
    from harness import peaks, pool_cost

    pk = peaks.peaks_for("TPU v5 lite")
    assert pool_cost.build_field_muls_per_key() == 64 * (14 * 9 + 4 * 8) \
        + 960 * 7 + 265
    ops, moved = pool_cost.cost("build", 3)
    assert ops == 3 * pool_cost.build_field_muls_per_key() * 2048
    assert moved == 3 * (64 + 1024 * 96 * 2)
    assert pool_cost.cost("update", 2) == (0.0, 2 * (3 * 1024 * 96 * 2 + 4))
    assert pool_cost.least_seconds("build", 1, pk)[1] == "memory"
    assert pool_cost.least_seconds("update", 1, pk)[1] == "memory"
    assert pool_cost.least_seconds("ladder", 1, pk)[1] == "compute"
    # a key's table cannot be written faster than HBM takes its bytes
    assert pool_cost.least_seconds("build", 1, pk)[0] >= 196608 / 819e9
    with pytest.raises(ValueError):
        pool_cost.cost("verify", 1)


def test_the_traced_stretchs_readers_on_a_hand_made_stretch(tmp_path):
    """Four calls as the set-up stretch makes them (128 lanes of ladder
    alone; 128 built; 256 and 8 resident) and a window of three programs,
    one of them ladder alone: the comb program's lanes are its own, and a
    call that ran no comb program adds none of its time to the window."""
    from harness import peaks, verify_cost
    from harness.observe import Observations
    from readers import pool_window_idle_share, trace_comb_lanes

    fields = ["seq", "t_recv0", "t_verdicts", "program", "program_lanes", "ran",
              "lanes_ladder"]
    ms = 1_000_000
    rows = [[1, 10 * ms, 20 * ms, 1, 128, "ladder_only", 128],
            [2, 30 * ms, 90 * ms, 2, 128, "with_build", 0],
            [3, 100 * ms, 120 * ms, 3, 256, "all_hit", 0],
            [4, 130 * ms, 150 * ms, 4, 8, "all_hit", 0],
            [5, 160 * ms, 170 * ms, 5, 200, "all_hit", 0],       # past the stop
            # the window
            [6, 1000 * ms, 1020 * ms, 6, 8, "all_hit", 0],
            [7, 1100 * ms, 1120 * ms, 7, 3, "ladder_only", 3],
            [8, 1200 * ms, 1290 * ms, 8, 8, "with_build", 1]]
    run = tmp_path / "run"
    (run / "trace").mkdir(parents=True)
    with open(run / "devd.spans.jsonl", "w") as f:
        f.write(json.dumps({"fields": fields, "count": 8, "ring_size": 64}) + "\n")
        f.writelines(json.dumps(r) + "\n" for r in rows)
    mods = [["jit__verify_impl(1)", 12 * ms, 4 * ms],
            ["jit__build_tables_impl(2)", 40 * ms, 38 * ms],
            ["jit__update_pool_impl(3)", 78 * ms, 1 * ms],
            ["jit__verify_comb_impl(4)", 80 * ms, 10 * ms],
            ["jit__verify_comb_impl(4)", 105 * ms, 11 * ms],
            ["jit__verify_comb_impl(4)", 135 * ms, 10 * ms]]
    obs = Observations(window_s=1.0, open_wall=0.9)
    obs.trace = {"dir": str(run / "trace"), "start_wall_ns": 5 * ms,
                 "stop_wall_ns": 155 * ms, "widths": [8, 256],
                 "extracted": {"window": [5 * ms, 155 * ms], "marks": [[0, 0]],
                               "devices": [{"modules": mods, "busy_ns": 74 * ms,
                                            "stretches": []}]}}
    obs.spans = [(r[1], r[2], r[4]) for r in rows]
    obs.counters = {"pool.builds": (3, 4), "pool.ladders": (5, 7)}
    dev = {"kind": "TPU v5 lite"}
    comb = {"kernel": "_verify_comb_impl"}
    # 128 + 256 + 8 lanes over 31 ms: not the 520 of every call in the stretch
    assert trace_comb_lanes.read(obs, {**comb, "as": "rate"}, dev) \
        == pytest.approx(392 / 0.031)
    least = verify_cost.least_seconds(392, 0, 0, peaks.peaks_for("TPU v5 lite"))[0]
    assert trace_comb_lanes.read(obs, {**comb, "as": "roofline_share"}, dev) \
        == pytest.approx(100 * least / 0.031)
    # the window: two comb programs of 8 lanes at 10 ms (the ladder-alone
    # call counts none), one build at 38 + 1 ms, two ladders at 4 ms
    idle = pool_window_idle_share.read(
        obs, {"comb": "_verify_comb_impl", "build": "_build_tables_impl",
              "update": "_update_pool_impl", "ladder": "jit__verify_impl"}, dev)
    assert idle == pytest.approx(100 * (1 - (0.020 + 0.039 + 0.008) / 1.0))
    # a program from before the records' pool fields: nothing, not an error
    with open(run / "devd.spans.jsonl", "w") as f:
        f.write(json.dumps({"fields": fields[:5], "count": 1, "ring_size": 64}) + "\n")
        f.write(json.dumps(rows[0][:5]) + "\n")
    assert trace_comb_lanes.read(obs, {**comb, "as": "rate"}, dev) is None


# -- the whole cell ------------------------------------------------------------------


def run_cell(scale, seconds, control="", trace=0):
    import run as bench_run

    argv = ["--workload", CELL, "--seed", str(2**31 + 77), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearsal", "--scale",
            json.dumps(scale)]
    if control:
        argv += ["--control", control]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    return line, over


def test_rehearsal_run_is_correct():
    line, over = run_cell(SMALL, 10)
    assert line["correct"] is True and not over, over
    assert line["attempted"] == 200 and line["failed"] == 0
    notes = line["notes"]
    assert notes["reads_that_found_a_record"] == notes["reads_answered"] > 0
    assert notes["load"]["records"] == 1024 and notes["load"]["blocks"] == 16
    assert notes["pool_at_close"]["resident_keys"] == 31
    assert min(notes["pool_in_window"][k] for k in (
        "lanes_first_sight", "builds", "evictions", "ladders")) > 0
    # one bucket; a pool of under 128 slots builds at its own size
    assert set(notes["miss_programs_s"]) == {"build_32", "update_32", "ladder_32"}
    assert {"commit_latency_p50_ms", "commit_latency_p95_ms", "read_latency_p50_ms",
            "setup_s"} <= set(line["metrics"])
    assert line["metrics"]["read_latency_p50_ms"]["value"] == \
        line["notes"]["read_latency_ms"]["p50"] > 0


def test_control_verifier_that_skips_verification():
    line, over = run_cell(SMALL, 8, control="accept-all")
    assert line["correct"] is False
    assert {"forged_writes_accepted.resident", "forged_writes_accepted.evicted",
            "forged_writes_accepted.never_seen"} <= over
    assert line["notes"]["forged_writes_accepted"] == 12


def test_a_store_that_was_never_loaded(monkeypatch):
    from harness import ycsb_load

    monkeypatch.setattr(ycsb_load, "install", lambda made, home: None)
    line, over = run_cell(SMALL, 8)
    assert line["correct"] is False
    assert "reads_of_a_loaded_record_that_found_none" in over
    assert line["notes"]["reads_that_found_a_record"] < line["notes"]["reads_answered"]


def test_against_a_daemon_that_announces_no_miss_programs():
    import run as bench_run

    closed = json.loads(json.dumps(SMALL))
    del closed["config"]["daemon"]["env"]["TENDERMINT_TPU_COMB_OPEN"]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", CELL, "--seed", "5", "--seconds", "6",
                             "--trace", "0", "--rehearsal", "--scale",
                             json.dumps(closed)])
    assert rc == 1 and buf.getvalue().strip() == ""
