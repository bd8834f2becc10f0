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
