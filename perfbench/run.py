#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it: `configs/<config>.json` (as BENCHMARK.json's `file` says),
`traffic/<traffic>.json`, `metrics/<metric>.json`. A configuration names
its `deployment`, which is a module in `scenarios/`; a metric names its
`reader`, a module in `readers/`, and the cells that read it (one entry
a metric, however many cells); where one cell reads it another way, the
metric's file gives that cell's reader under `by_workload`. A later PR
adds a cell or a metric by adding files and entries.

This process launches and reads; it never imports JAX. The device daemon
(started through `harness/devd_launcher.py`) is the one process that
holds the chip.

The last line of standard output is the result; nothing is printed there
when the run cannot stand (no chip, a process died, the repository is
not around the benchmark). `--rehearsal` (CPU daemon, for tests and
developers) and `--control <fault>` (a daemon whose verifier skips work,
for the output check's control runs) are never passed by the driver.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import signal
import subprocess
import sys
import time
import traceback
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WATCHDOG_S = 345


@dataclass
class Context:
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    control: str
    run_dir: str

    def start_trace(self, daemon, close_wall: float, length_s: float) -> dict:
        """Trace `length_s` seconds at the END of the window inside the
        daemon. Every execution of the kernel is some 22,000 device
        events, and writing them out costs the daemon about 3 s an
        execution: so the traced stretch is short, lies at the end, and
        `finish_trace` waits for the writing after the close. The stretch
        is counted from the launcher's answer (starting the profiler
        takes tens of milliseconds). The launcher ends it at its 12th
        verifier call; at the harness's stop, one that holds no call yet
        stays open until the first one lands (`devd_launcher.Recorder`)."""
        start_at = close_wall - length_s - 0.3
        while time.time() < start_at:
            time.sleep(0.02)
        tdir = os.path.join(self.run_dir, "trace")
        a = daemon.request("start_trace", dir=tdir)
        time.sleep(max(0.0, min(length_s, close_wall - 0.05 - time.time())))
        pending = daemon.post("stop_trace")
        return {"dir": tdir, "start_wall_ns": a["start_wall_ns"],
                "pending": pending}

    def finish_trace(self, daemon, trace: dict) -> dict:
        b = daemon.wait_ack(trace.pop("pending"), "stop_trace", timeout=240)
        trace["stop_wall_ns"] = b["stop_wall_ns"]
        trace["stop_took_s"] = (b["written_wall_ns"] - b["stop_wall_ns"]) / 1e9
        trace["traced_calls"] = b["traced_calls"]
        if b["traced_calls"] < 1:
            from harness import procs

            raise procs.HarnessError(
                "the traced stretch holds no verifier call: none came within "
                f"{b['waited_for_call_s']:.1f} s of the harness's stop")
        return trace


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def reduce_trace(trace: dict) -> None:
    """xplane -> plain events, in a process of its own (the only code of
    the benchmark that imports jax, pinned to the CPU, no backend)."""
    out = os.path.join(os.path.dirname(trace["dir"]), "trace_events.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "harness", "trace_reduce.py"),
         trace["dir"], out], env=env, capture_output=True, text=True, timeout=200)
    if r.returncode != 0:
        raise RuntimeError(f"trace reduction failed: {r.stderr[-2000:]}")
    trace["extracted"] = load_json(out)


def metric_reader(spec: dict, cell: str) -> tuple[str, dict]:
    """The reader and its parameters that a metric's file gives for one
    cell: the cell's own under `by_workload`, else the metric's."""
    own = spec.get("by_workload", {}).get(cell, spec)
    return own["reader"], own.get("params", {})


def per_layer_metrics(bench: dict, cell: str, obs, device: dict) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        spec = load_json(os.path.join(BENCH, "metrics", m["name"] + ".json"))
        name, params = metric_reader(spec, cell)
        value = importlib.import_module("readers." + name).read(obs, params, device)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", default="",
                    choices=("", "accept-all", "half-batch"))
    ap.add_argument("--scale", default="",
                    help="JSON overrides of configuration keys, for tests: "
                         "never passed in a measured run")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "tendermint_tpu")):
        print("perfbench needs the repository it sits in", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    scale = json.loads(args.scale) if args.scale else {}
    for k, v in scale.get("config", {}).items():
        config[k] = v
    for k, v in scale.get("traffic", {}).items():
        traffic[k] = v
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    from harness import procs

    ctx = Context(workload=cell, config=config, traffic=traffic,
                  seed=int(args.seed), seconds=seconds, trace=bool(args.trace),
                  rehearsal=args.rehearsal, control=args.control,
                  run_dir=procs.run_dir(cell["name"]))

    def on_alarm(signum, frame):
        raise procs.HarnessError(f"the run passed its bound of {WATCHDOG_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(WATCHDOG_S)
    try:
        scenario = importlib.import_module("scenarios." + config["deployment"])
        res = scenario.run(ctx)
        procs.stop_all()
        procs.no_jax_here()
        obs = res["obs"]
        breakdown = None
        if ctx.trace and obs.trace:
            reduce_trace(obs.trace)
            from harness import trace_reduce

            red = trace_reduce.reduce(obs.trace["extracted"], spans=obs.spans,
                                      compiles=obs.compiles_in_window)
            # (a CPU daemon's profile has no device plane: a rehearsal
            # reads 0 for both)
            if not ctx.rehearsal and (red["window_s"] <= 0 or red["busy_s"] <= 0):
                raise procs.HarnessError(
                    f"the traced stretch of {obs.trace['traced_calls']} verifier "
                    f"calls holds no device operation (window_s "
                    f"{red['window_s']}, busy_s {red['busy_s']})")
            res["notes"]["traced_calls"] = obs.trace["traced_calls"]
            res["device"]["busy_s"] = red["busy_s"]
            res["device"]["window_s"] = red["window_s"]
            breakdown = {"device_ops": red.get("device_ops", []),
                         "idle_gaps": red.get("idle_gaps", [])}
            lo = int(obs.open_wall * 1e9)
            whole = "widths" in obs.trace and trace_reduce.window_busy(
                obs.trace["extracted"], obs.spans, lo,
                lo + int(obs.window_s * 1e9), obs.trace["widths"])
            if whole:
                # the stretch is a fraction of the window: the same split
                # over all of it, from every verifier call's span
                res["notes"]["whole_window"] = whole
                breakdown["idle_gaps"] = [
                    ["whole_window:no_request_at_daemon",
                     whole["window_s"] - whole["in_flight_s"]],
                    ["whole_window:call_in_flight_device_idle",
                     max(0.0, whole["in_flight_s"] - whole["busy_s"])],
                ] + breakdown["idle_gaps"][:8]
        if ctx.trace:
            metrics = per_layer_metrics(bench, cell["name"], obs, res["device"])
        else:
            metrics = {}
            for m in bench["end_to_end"]:
                if "workloads" in m and cell["name"] not in m["workloads"]:
                    continue
                if m["name"] in res["end_to_end"]:
                    metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                          "unit": m["unit"]}
    except BaseException as exc:  # noqa: BLE001 — no result, exit != 0
        signal.alarm(0)
        procs.stop_all()
        traceback.print_exc(file=sys.stderr)
        print(f"perfbench: no result: {type(exc).__name__}: {exc}"[:4000],
              file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)

    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in res["comparisons"]}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    line = {
        "correct": correct,
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": res["device"],
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["workload"] = cell["name"]
    line["seed"] = ctx.seed
    line["seconds"] = seconds
    line["notes"] = res.get("notes", {})
    if ctx.trace:
        line["end_to_end_of_this_traced_run"] = res["end_to_end"]
    line["compared"] = compared
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}"
              + ("" if c["value"] <= c["limit"] else "   <-- over"),
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
