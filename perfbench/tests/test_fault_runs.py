"""The whole of a run, on a CPU daemon at a size a test can hold, with
the timed path broken underneath: `correct` has to come out false for
each fault a cell can have, and true for the sound run.

Slow (each run boots real node processes and a daemon that compiles its
kernels for the CPU backend on first use, then finds them in
.jax_cache): about 1-2 minutes a case. Run with
    python3 -m pytest perfbench/tests/test_fault_runs.py -q
The harness's look for a chip is skipped with --rehearsal; nothing else.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

NET4 = {"traffic": {"rate_per_s": 10, "signers": 6, "lead_in_s": 1.0,
                    "readback_sample": 12, "forged_writes": 4},
        "config": {"daemon": {"env": {"TENDERMINT_DEVD_KERNEL": "comb",
                                      "TENDERMINT_DEVD_WARM": ""},
                              "warm_buckets": [8, 16], "warm_passes": 2}}}
CATCHUP = {"config": {"validators": 40},
           "traffic": {"expected_blocks_per_s": 30, "peers": 2,
                       "state_sample_keys": 20}}


@pytest.fixture
def staged_catchup(monkeypatch):
    """`catchup1000.commits` is staged, not a cell (PERF.md section 7): its
    entries are read into BENCHMARK.json as a later PR would paste them."""
    import run as bench_run

    real = bench_run.load_json

    def load_json(path):
        out = real(path)
        if os.path.basename(path) == "BENCHMARK.json":
            staged = real(os.path.join(os.path.dirname(__file__), "data",
                                       "staged_catchup.json"))
            for key in ("configs", "workloads", "end_to_end", "per_layer"):
                out[key] = out[key] + staged[key]
        return out

    monkeypatch.setattr(bench_run, "load_json", load_json)


def run_cell(workload, scale, seconds, control="", patch=None, monkeypatch=None):
    import run as bench_run
    from harness import rpc

    if patch is not None:
        real = rpc.call

        def call(addr, method, params=None, timeout=10.0):
            return patch(method, params, real(addr, method, params, timeout))

        monkeypatch.setattr(rpc, "call", call)
    argv = ["--workload", workload, "--seed", str(2**31 + 99), "--seconds",
            str(seconds), "--trace", "0", "--rehearsal", "--scale", json.dumps(scale)]
    if control:
        argv += ["--control", control]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(argv)
    assert rc == 0, buf.getvalue()[-2000:]
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    return line, over


def test_net4_sound_run_is_correct():
    line, over = run_cell("net4.steady", NET4, 6)
    assert line["correct"] is True and not over
    assert line["attempted"] == 60 and line["failed"] == 0
    assert list(line)[-1] == "compared"


def test_net4_control_verifier_that_skips_verification():
    line, over = run_cell("net4.steady", NET4, 6, control="accept-all")
    assert line["correct"] is False
    assert "forged_writes_accepted" in over


def test_net4_answer_altered_where_it_is_read(monkeypatch):
    def alter(method, params, res):
        if method == "abci_query" and res["response"].get("value"):
            v = res["response"]["value"]
            res["response"]["value"] = v[:-2] + ("00" if v[-2:] != "00" else "01")
        return res

    line, over = run_cell("net4.steady", NET4, 6, patch=alter, monkeypatch=monkeypatch)
    assert line["correct"] is False and "readback_mismatches" in over


def test_net4_state_left_unchanged(monkeypatch):
    """Acknowledged, but neither in the block nor in the app."""
    def drop(method, params, res):
        if method == "block" and res["block"]["data"]["txs"]:
            res["block"]["data"]["txs"] = res["block"]["data"]["txs"][1:]
        if method == "abci_query":
            res["response"]["value"] = ""
        return res

    line, over = run_cell("net4.steady", NET4, 6, patch=drop, monkeypatch=monkeypatch)
    assert line["correct"] is False
    assert {"acked_writes_not_in_their_block", "readback_mismatches"} <= over


def test_catchup_sound_run_is_correct(staged_catchup):
    line, over = run_cell("catchup1000.commits", CATCHUP, 8)
    assert line["correct"] is True and not over
    assert line["attempted"] > 0


@pytest.mark.parametrize("control,number", [
    ("accept-all", "verdict_mismatches_vs_plain_ed25519"),
    ("half-batch", "verdict_mismatches_vs_plain_ed25519"),
])
def test_catchup_controls(control, number, staged_catchup):
    line, over = run_cell("catchup1000.commits", CATCHUP, 8, control=control)
    assert line["correct"] is False and number in over


def test_catchup_answer_altered_and_state_unchanged(monkeypatch, staged_catchup):
    seen = {"n": 0}

    def alter(method, params, res):
        if method == "blockchain":
            seen["n"] += 1
            if seen["n"] == 1:    # the syncing node's first page of hashes
                m = res["block_metas"][0]
                h = m["block_id"]["hash"]
                m["block_id"]["hash"] = ("0" if h[0] != "0" else "1") + h[1:]
        if method == "abci_query":
            res["response"]["value"] = ""
        return res

    line, over = run_cell("catchup1000.commits", CATCHUP, 8, patch=alter,
                          monkeypatch=monkeypatch)
    assert line["correct"] is False
    assert {"block_hash_mismatches", "state_readback_mismatches"} <= over
