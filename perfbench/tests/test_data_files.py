"""Every data file parses, every name BENCHMARK.json gives resolves to a
file, and each per-layer metric's cells all report the end-to-end metric
it moves."""

import importlib
import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path):
    with open(path) as f:
        return json.load(f)


def bench():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def bench_run():
    import run

    return run


def test_benchmark_json_shape():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))


def test_every_name_resolves_to_a_file():
    b = bench()
    for c in b["configs"]:
        cfg = load(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        importlib.import_module("scenarios." + cfg["deployment"])
        for key in ("source", "assumed", "reduced", "guarantees", "chips",
                    "chip_mapping", "why"):
            assert key in cfg, (c["name"], key)
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        mix = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert mix["name"] == w["traffic"]
    for m in b["per_layer"]:
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        for key in ("name", "unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == m[key], (m["name"], key)
        assert hasattr(importlib.import_module("readers." + spec["reader"]), "read")
    # a metric file that BENCHMARK.json does not name belongs to the staged
    # cell (tests/data/staged_catchup.json), whose entries resolve alike
    staged = load(os.path.join(BENCH, "tests", "data", "staged_catchup.json"))
    for m in staged["per_layer"]:
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert {k: spec[k] for k in m} == m
        assert hasattr(importlib.import_module("readers." + spec["reader"]), "read")
    for c, w in zip(staged["configs"], staged["workloads"]):
        assert load(os.path.join(ROOT, c["file"]))["name"] == c["name"] == w["config"]
        assert load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))["name"] == w["traffic"]
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))}
    assert on_disk == {m["name"] for m in b["per_layer"] + staged["per_layer"]}


def test_each_metric_moves_a_metric_its_cells_report():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    reported = {}
    for m in b["end_to_end"]:
        reported[m["name"]] = set(m.get("workloads", cells))
    for w in cells:
        assert "setup_s" in reported and w in reported["setup_s"]
        assert any(w in ws for n, ws in reported.items() if n != "setup_s")
        assert any(w in m.get("workloads", cells) for m in b["per_layer"])
    for m in b["per_layer"]:
        assert m["moves"] in reported, m["name"]
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in reported[m["moves"]], (m["name"], w)


def test_net4_config_carries_the_upstream_timeouts():
    cfg = load(os.path.join(BENCH, "configs", "net4-signedkv.json"))
    assert cfg["consensus"] == {
        "timeout_propose": 3.0, "timeout_propose_delta": 0.5,
        "timeout_prevote": 1.0, "timeout_prevote_delta": 0.5,
        "timeout_precommit": 1.0, "timeout_precommit_delta": 0.5,
        "timeout_commit": 1.0, "skip_timeout_commit": False}
    from tendermint_tpu.config.config import ConsensusConfig

    shipped = ConsensusConfig()
    for k, v in cfg["consensus"].items():
        assert getattr(shipped, k) == v, k


def test_one_entry_a_metric_over_the_cells_that_read_it():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    assert 1 <= len(b["per_layer"]) <= 60
    seen = set()
    for m in b["per_layer"]:
        # no cell's suffix: the cells are the entry's `workloads`
        assert "." not in m["name"] and m["workloads"]
        assert set(m["workloads"]) <= cells and \
            len(set(m["workloads"])) == len(m["workloads"])
        spec = load(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        assert set(spec.get("by_workload", {})) <= set(m["workloads"])
        for cell in m["workloads"]:
            reader, params = bench_run().metric_reader(spec, cell)
            assert hasattr(importlib.import_module("readers." + reader), "read")
            key = (m["name"], reader, json.dumps(params, sort_keys=True))
            seen.add(key)
    # no two entries share a name, a reader and its parameters
    assert len({k[0] for k in seen}) == len(b["per_layer"])


def test_the_fold_leaves_every_cell_reading_what_it_read():
    """perfbench/tests/data/per_layer_fold.json: the 128 entries of one
    cell each that the benchmark had, and the name each is read under
    now. Every cell reads exactly the reader and parameters it read
    before."""
    b = bench()
    fold = load(os.path.join(BENCH, "tests", "data", "per_layer_fold.json"))["entries"]
    assert len(fold) == 128 == len({r["old"] for r in fold})
    entries = {m["name"]: m for m in b["per_layer"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for r in fold:
        assert r["old"].startswith(r["new"] + ".") or r["as"] == "end_to_end"
        if r["as"] == "end_to_end":
            # the p50 of the window's reads, now computed by the scenario
            assert r["cell"] in e2e[r["new"]]["workloads"]
            assert (r["reader"], r["params"]) == (
                "percentile", {"q": 50, "series": "read_latency_ms"})
            continue
        m = entries[r["new"]]
        assert r["cell"] in m["workloads"], r["old"]
        spec = load(os.path.join(BENCH, "metrics", r["new"] + ".json"))
        assert bench_run().metric_reader(spec, r["cell"]) == (r["reader"], r["params"])
    # and nothing else changed name: every (cell, metric) pair of the fold
    # is one of the benchmark's, the new entries apart
    old_pairs = {(r["new"], r["cell"]) for r in fold if r["as"] == "per_layer"}
    new_pairs = {(n, c) for n, m in entries.items() for c in m["workloads"]}
    assert old_pairs <= new_pairs


def test_per_layer_metrics_takes_a_cells_own_reader(monkeypatch):
    """`device_idle_share` and the two `verify_kernel_*` metrics read
    `ycsb-a.steady` by a reader of its own (`by_workload`), every other
    cell by the metric's."""
    run = bench_run()
    b = bench()
    picked = []

    def fake(name):
        def read(obs, params, device):
            picked.append((name, params))
            return 1.0
        return read

    names = ("window_idle_share", "pool_window_idle_share", "trace_kernel_rate",
             "trace_kernel_roofline", "trace_comb_lanes")
    for name in names:
        monkeypatch.setattr(importlib.import_module("readers." + name), "read",
                            fake(name))
    mine = {"device_idle_share", "verify_kernel_sigs_per_s",
            "verify_kernel_roofline_share"}
    only = {**b, "per_layer": [m for m in b["per_layer"] if m["name"] in mine]}
    assert len(only["per_layer"]) == 3
    out = run.per_layer_metrics(only, "ycsb-a.steady", None, {})
    assert set(out) == mine
    assert sorted(picked, key=str) == sorted([
        ("pool_window_idle_share", {"comb": "_verify_comb_impl",
                                    "build": "_build_tables_impl",
                                    "update": "_update_pool_impl",
                                    "ladder": "jit__verify_impl"}),
        ("trace_comb_lanes", {"as": "rate", "kernel": "_verify_comb_impl"}),
        ("trace_comb_lanes", {"as": "roofline_share", "kernel": "_verify_comb_impl"}),
    ], key=str)
    picked.clear()
    run.per_layer_metrics(only, "net4.steady", None, {})
    assert sorted(n for n, _p in picked) == [
        "trace_kernel_rate", "trace_kernel_roofline", "window_idle_share"]
