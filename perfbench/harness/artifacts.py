"""What the program itself wrote under the run's directory when its
processes stopped, for the per-layer readers: the daemon's ring of
per-call records (`devd.spans.jsonl`, beside its socket) and a node's
flight-recorder dump with reason `stop` (its `consensus_traces`).

A reader gets `obs` only; the run's directory is the parent of
`obs.trace["dir"]` (readers run in traced runs, and there it is set).
A file that should be there and is not raises with the path looked for.
Only a program from before these records (the parent commit a new
metric is first measured beside) yields nothing: it is known by its
lacking `tendermint_tpu/devd_spans.py`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PHASES = ("decode", "marshal", "dispatch", "device_wait", "reply")


def program_keeps_records() -> bool:
    return os.path.exists(os.path.join(ROOT, "tendermint_tpu", "devd_spans.py"))


def run_dir(obs) -> str:
    return os.path.dirname(obs.trace["dir"])


def window_ns(obs) -> tuple[int, int]:
    lo = int(obs.open_wall * 1e9)
    return lo, lo + int(obs.window_s * 1e9)


def spans_path(run: str) -> str:
    """Beside the daemon's socket: in the run's directory, or wherever
    `procs.sock_path` fell back to (the `devd listening on` line says)."""
    path = os.path.join(run, "devd.spans.jsonl")
    if os.path.exists(path):
        return path
    try:
        with open(os.path.join(run, "devd.log"), errors="replace") as f:
            m = re.search(r"devd listening on (\S+)", f.read())
    except OSError:
        m = None
    if m and m.group(1).endswith(".sock"):
        return m.group(1)[:-len(".sock")] + ".spans.jsonl"
    return path


def load_spans(path: str) -> tuple[dict, list[dict]]:
    """(header, records as dicts by the header's field names)."""
    with open(path) as f:
        header = json.loads(f.readline())
        fields = header["fields"]
        return header, [dict(zip(fields, json.loads(line)))
                        for line in f if line.strip()]


def window_records(obs) -> list[dict] | None:
    """The daemon's records whose t_recv0 lies in the window; None for a
    program that keeps none."""
    if not program_keeps_records():
        return None
    cached = obs.trace.get("call_records")
    if cached is None:
        path = spans_path(run_dir(obs))
        if not os.path.exists(path):
            raise FileNotFoundError(f"the daemon's call records: {path}")
        header, records = load_spans(path)
        if header["count"] > header["ring_size"]:
            raise RuntimeError(f"{path}: the ring wrapped ({header['count']} "
                               f"records through {header['ring_size']} slots)")
        lo, hi = window_ns(obs)
        cached = obs.trace["call_records"] = [
            r for r in records if lo <= r["t_recv0"] < hi]
    return cached


def phase_ms(record: dict, phase: str) -> float:
    names = ("t_recv0", "t_decoded", "t_marshalled", "t_dispatched",
             "t_verdicts", "t_replied")
    i = PHASES.index(phase)
    return (record[names[i + 1]] - record[names[i]]) / 1e6


def stop_dump(run: str, node: int = 0) -> str:
    pattern = os.path.join(run, f"node{node}", "flightrec", "dump-*-stop*.json")
    hits = sorted(glob.glob(pattern))
    if not hits:
        raise FileNotFoundError(f"node{node}'s stop dump: {pattern}")
    return hits[-1]


def window_heights(obs, node: int = 0) -> list[dict] | None:
    """Node `node`'s per-height traces whose started_at lies in the
    window, from its stop dump; None for a program that dumps none."""
    if not program_keeps_records():
        return None
    cached = obs.trace.get("dump_heights")
    if cached is None:
        path = stop_dump(run_dir(obs), node)
        with open(path) as f:
            traces = json.load(f)["consensus_traces"]
        lo = obs.open_wall
        cached = obs.trace["dump_heights"] = [
            t for t in traces if lo <= t.get("started_at", 0) < lo + obs.window_s]
    return cached


def read_annotations(obs) -> dict:
    """The trace's `devd.*` annotations and the device's module events,
    read from the .xplane.pb in a process of its own (this one never
    imports JAX), once a run."""
    cached = obs.trace.get("annotations")
    if cached is None:
        out = os.path.join(run_dir(obs), "trace_annotations.json")
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "trace_annotations.py"),
             obs.trace["dir"], out],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=200)
        if r.returncode != 0:
            raise RuntimeError(f"reading the annotations failed: {r.stderr[-2000:]}")
        with open(out) as f:
            cached = obs.trace["annotations"] = json.load(f)
    return cached
