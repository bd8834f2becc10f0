"""The per-height traces of EVERY node of a run, from the nodes'
flight-recorder dumps with reason `stop` (`harness/artifacts.py` reads
node 0's): for the readers that sum or compare over the fleet."""

from __future__ import annotations

import json
import os
import re

from harness import artifacts


def window_heights_by_node(obs) -> dict[int, list[dict]] | None:
    """node index -> its traces whose started_at lies in the window; None
    for a program that dumps none."""
    if not artifacts.program_keeps_records():
        return None
    cached = obs.trace.get("fleet_heights")
    if cached is None:
        run = artifacts.run_dir(obs)
        nodes = sorted(int(m.group(1)) for m in
                       (re.fullmatch(r"node(\d+)", d) for d in os.listdir(run))
                       if m)
        lo = obs.open_wall
        cached = {}
        for i in nodes:
            with open(artifacts.stop_dump(run, i)) as f:
                traces = json.load(f)["consensus_traces"]
            cached[i] = [t for t in traces
                         if lo <= t.get("started_at", 0) < lo + obs.window_s]
        obs.trace["fleet_heights"] = cached
    return cached
