#!/usr/bin/env python3
"""The daemon's own annotations in a profiler trace, beside the device's
programs, on the trace's one clock.

`extract(xplane_path)` (needs `jax.profiler.ProfileData`, no backend):
every `devd.<phase>` annotation with its `seq` and `lanes`, the
`devd.clock:<wall_ns>` marks, and the `XLA Modules` events of the device
planes. A trace of the CPU backend (a rehearsal) has no device plane:
there the executor threads' `ThunkExecutor::Execute` events stand for the
programs, as `host_exec`. Run as a script in a process of its own:
    trace_annotations.py <trace dir> <out.json>

`verdict_lags(extracted, kernel)` (pure Python): for every call that has
both its `devd.dispatch` and its `devd.device_wait` annotation, the end
of `devd.device_wait` less the end of the kernel's program that ran
inside that call: how long the verdicts took to reach the host after the
device had them. No offset is applied anywhere: one clock.
"""

from __future__ import annotations

import json
import os
import re
import sys

PREFIX = "devd."
CLOCK = "devd.clock:"


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    annotations, clocks, modules, host_exec = [], [], [], []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device:
                if line.name == "XLA Modules" and "CUSTOM" not in plane.name:
                    modules += [[ev.name, float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns)]
                                for ev in line.events]
                continue
            executor = line.name.startswith("tf_XLA")
            for ev in line.events:
                name = ev.name
                if executor:
                    if name == "ThunkExecutor::Execute":
                        host_exec.append([float(ev.start_ns),
                                          float(ev.start_ns + ev.duration_ns)])
                elif name.startswith(CLOCK):
                    clocks.append([int(name[len(CLOCK):]), float(ev.start_ns)])
                elif name.startswith(PREFIX):
                    stats = dict(ev.stats)
                    annotations.append([
                        name[len(PREFIX):], int(stats.get("seq", 0)),
                        int(stats.get("lanes", 0)), float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns)])
    return {"annotations": annotations, "clocks": clocks, "modules": modules,
            "host_exec": host_exec}


def calls(extracted: dict) -> dict[int, dict]:
    """seq -> {phase: (start, end)}, the first annotation of a name."""
    out: dict[int, dict] = {}
    for phase, seq, _lanes, start, end in extracted.get("annotations") or []:
        out.setdefault(seq, {}).setdefault(phase, (start, end))
    return out


def joined(extracted: dict, kernel: str) -> list[dict]:
    """Each call of the trace that holds one program of the kernel:
    {seq, dispatch_start, wait_end, program_start, program_end}. The
    device runs programs in the order the calls dispatched them, so of
    several inside a call's stretch the earliest not yet given away is
    its own."""
    pat = re.compile(kernel)
    progs = sorted(([s, e] for name, s, e in extracted.get("modules") or []
                    if pat.search(name)))
    on_device = bool(progs)
    if not on_device:
        progs = sorted(extracted.get("host_exec") or [])
    whole = [(seq, c["dispatch"][0], c["dispatch"][1], c["device_wait"][1])
             for seq, c in calls(extracted).items()
             if "dispatch" in c and "device_wait" in c]
    taken: set[int] = set()
    out = []
    for seq, d0, d1, w1 in sorted(whole, key=lambda c: c[2]):
        inside = [i for i, (s, e) in enumerate(progs) if s >= d0 and e <= w1]
        if on_device:
            inside = [i for i in inside if i not in taken][:1]
        if not inside:
            continue
        taken.update(inside)
        out.append({"seq": seq, "dispatch_start": d0, "wait_end": w1,
                    "program_start": min(progs[i][0] for i in inside),
                    "program_end": max(progs[i][1] for i in inside)})
    return out


def verdict_lags(extracted: dict, kernel: str) -> list[float]:
    """ms, one a call."""
    return [(c["wait_end"] - c["program_end"]) / 1e6
            for c in joined(extracted, kernel)]


def main() -> None:
    from trace_reduce import find_xplane

    trace_dir, out_path = sys.argv[1], sys.argv[2]
    ex = extract(find_xplane(trace_dir))
    with open(out_path + ".tmp", "w") as f:
        json.dump(ex, f)
    os.replace(out_path + ".tmp", out_path)


if __name__ == "__main__":
    main()
