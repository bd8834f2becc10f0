#!/usr/bin/env python3
"""From a profiler trace to the device's numbers.

Two stages, so that the arithmetic can be checked on a small recorded
trace without JAX:

1. `extract(xplane_path)` (needs `jax.profiler.ProfileData`, nothing
   else of JAX, no backend): the device planes' events and the
   benchmark's own `bench_mark:<wall_ns>` annotations, as plain lists.
   Run as a script in a process of its own:
       trace_reduce.py <trace dir> <out.json>
2. `reduce(extracted, ...)` (pure Python): busy seconds as the union of
   the intervals in which an operation ran on a device, averaged over
   the devices; the traced window; per-operation totals; the time of the
   kernels whose name matches a pattern; the idle gaps, each split by
   what the daemon's verifier spans say the host was doing.
   `window_busy(...)` carries the traced stretch over the whole window:
   every verifier call of the window (the launcher's spans) at the
   device time the trace read for a call of its width.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

MARK = "bench_mark:"
# lines of a device plane that hold operations (as against steps, or
# annotations copied from the host)
OP_LINES = ("XLA Ops", "XLA Modules")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


COALESCE_NS = 1000.0   # gaps shorter than this are not listed as gaps


def extract(xplane_path: str) -> dict:
    """The device planes of a trace, made small: a traced second of this
    system's kernel is two million `XLA Ops` events (about 22,000 an
    execution), so the operations' intervals are reduced here, exactly,
    to the busy time inside the marked window and to the list of busy
    stretches (stretches less than a microsecond apart listed as one);
    the `XLA Modules` events, one per executed program, are kept whole."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    marks, devices = [], []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:"):
                break
            for ev in line.events:
                if ev.name.startswith(MARK):
                    marks.append([int(ev.name[len(MARK):]), float(ev.start_ns)])
    m0 = min((t for _w, t in marks), default=None)
    m1 = max((t for _w, t in marks), default=None)
    for plane in data.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        by_name = {line.name: line for line in plane.lines}
        ops = next((by_name[n] for n in OP_LINES if n in by_name), None)
        if ops is None:
            continue
        ivs = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                     for ev in ops.events)
        lo = m0 if m0 is not None else (ivs[0][0] if ivs else 0.0)
        hi = m1 if m1 is not None else (max(e for _s, e in ivs) if ivs else 0.0)
        busy, stretches, edge = 0.0, [], None
        for s, e in ivs:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if edge is None or s > edge:
                busy += e - s
                if stretches and s - stretches[-1][1] < COALESCE_NS:
                    stretches[-1][1] = e
                else:
                    stretches.append([s, e])
                edge = e
            elif e > edge:
                busy += e - edge
                stretches[-1][1] = e
                edge = e
        mods = by_name.get("XLA Modules")
        devices.append({
            "name": plane.name, "op_line": ops.name, "op_events": len(ivs),
            "busy_ns": busy, "stretches": stretches,
            "modules": [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for ev in (mods.events if mods is not None else [])],
        })
    return {"devices": devices, "marks": marks, "window": [m0, m1]}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clock_offset_ns(extracted: dict) -> float | None:
    """trace clock minus wall clock, from the benchmark's marks."""
    marks = extracted.get("marks") or []
    if not marks:
        return None
    offs = sorted(t - w for w, t in marks)
    return offs[len(offs) // 2]


def reduce(extracted: dict, spans: list | None = None,
           compiles: list | None = None, kernel_pattern: str = "") -> dict:
    """The numbers of one traced window (the stretch between the
    launcher's two marks).

    spans: the launcher's verifier spans (start_ns, end_ns, lanes) on the
    wall clock; compiles: (end_wall_ns, seconds). Both optional."""
    devices = extracted.get("devices") or []
    w0, w1 = extracted.get("window") or (None, None)
    if not devices or w0 is None or w1 is None or w1 <= w0:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0}
    off = clock_offset_ns(extracted)
    pat = re.compile(kernel_pattern) if kernel_pattern else None
    op_totals: dict[str, float] = {}
    kernel_ns, kernel_events = 0.0, 0
    for dev in devices:
        for name, s, d in dev["modules"]:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            short = name.split("(")[0]
            op_totals[short] = op_totals.get(short, 0.0) + (e2 - s2) / 1e9
            if pat is not None and pat.search(name):
                kernel_ns += e2 - s2
                kernel_events += 1
    gaps, edge = [], w0
    for s, e in devices[0]["stretches"]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if w1 > edge:
        gaps.append((edge, w1))
    n_dev = len(devices)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in devices) / n_dev / 1e9,
        "devices": n_dev,
        "device_ops": sorted(([k, v] for k, v in op_totals.items()),
                             key=lambda kv: -kv[1])[:10],
        "kernel_s": kernel_ns / 1e9 / n_dev, "kernel_events": kernel_events,
        "idle_gaps": attribute_gaps(gaps, off, spans or [], compiles or []),
    }


def width_of(lanes: int, widths: list[int]) -> int:
    """The least of the widths the daemon pads a batch to that holds it."""
    for w in sorted(widths):
        if lanes <= w:
            return w
    return lanes


def window_busy(extracted: dict, spans: list, open_ns: int, close_ns: int,
                widths: list[int]) -> dict | None:
    """The device's busy seconds over the WHOLE window. A trace holds a
    fraction of a second of this system (PERF.md), one phase of a block
    interval; the launcher's spans hold every verifier call of the window
    with its lanes. Each program the trace saw run is given to the call
    whose span holds its start (of several, the earliest that has none
    yet: the device serves them in order); a call of the window then counts at the mean
    device time of the traced calls of its width, or of all traced calls
    where the stretch held none that wide. Nothing where the trace and
    the spans share no call."""
    devices = extracted.get("devices") or []
    off = clock_offset_ns(extracted)
    w0, w1 = extracted.get("window") or (None, None)
    if not devices or off is None or w0 is None:
        return None
    near = sorted(s for s in spans if s[1] + off >= w0 and s[0] + off <= w1)
    per_call: dict[int, float] = {}
    for _name, start, dur in sorted(devices[0]["modules"], key=lambda m: m[1]):
        if not w0 <= start <= w1:
            continue
        wall = start - off
        held_by = [k for k, (s0, s1, _n) in enumerate(near) if s0 <= wall <= s1]
        if held_by:
            k = next((k for k in held_by if k not in per_call), held_by[0])
            per_call[k] = per_call.get(k, 0.0) + dur
    if not per_call:
        return None
    by_width: dict[int, list[float]] = {}
    for k, ns in per_call.items():
        by_width.setdefault(width_of(near[k][2], widths), []).append(ns)
    mean = {w: sum(v) / len(v) for w, v in by_width.items()}
    mean_all = sum(per_call.values()) / len(per_call)
    calls = [s for s in spans if open_ns <= s[0] < close_ns]
    busy_ns = sum(mean.get(width_of(n, widths), mean_all) for _a, _b, n in calls)
    in_flight = _union([(max(a, open_ns), min(b, close_ns)) for a, b, _n in calls])
    return {
        "window_s": (close_ns - open_ns) / 1e9, "busy_s": busy_ns / 1e9,
        "in_flight_s": sum(b - a for a, b in in_flight if b > a) / 1e9,
        "calls": len(calls), "calls_traced": len(per_call),
        "calls_of_traced_widths": sum(1 for _a, _b, n in calls
                                      if width_of(n, widths) in mean),
        "device_ms_by_width": {str(w): v / 1e6 for w, v in sorted(mean.items())},
    }


def attribute_gaps(gaps, off, spans, compiles) -> list:
    """Split every idle gap of the device by what the daemon was doing:
    `compiling` (a program was being compiled or loaded), `chunk in
    flight` (a batch was inside the daemon's verifier: marshalling,
    transfer, dispatch, waiting for the verdicts to come back), `no
    request` (nothing was asked of the daemon). Returns the 10 longest
    entries: the three totals first, then single gaps."""
    busy_host = [(s + off, e + off, "chunk_in_flight_host_side")
                 for s, e, _n in spans]
    busy_host += [(end + off - sec * 1e9, end + off, "compiling")
                  for end, sec in compiles]
    totals = {"no_request_at_daemon": 0.0, "chunk_in_flight_host_side": 0.0,
              "compiling": 0.0}
    singles = []
    for g0, g1 in gaps:
        parts = {"chunk_in_flight_host_side": [], "compiling": []}
        for s, e, what in busy_host:
            s2, e2 = max(s, g0), min(e, g1)
            if e2 > s2:
                parts[what].append((s2, e2))
        comp = _union(parts["compiling"])
        comp_ns = sum(e - s for s, e in comp)
        both = _union(parts["chunk_in_flight_host_side"] + parts["compiling"])
        both_ns = sum(e - s for s, e in both)
        flight_ns = both_ns - comp_ns
        none_ns = (g1 - g0) - both_ns
        totals["compiling"] += comp_ns / 1e9
        totals["chunk_in_flight_host_side"] += flight_ns / 1e9
        totals["no_request_at_daemon"] += none_ns / 1e9
        what = max((("compiling", comp_ns),
                    ("chunk_in_flight_host_side", flight_ns),
                    ("no_request_at_daemon", none_ns)), key=lambda kv: kv[1])[0]
        singles.append(["one_gap:mostly_" + what, (g1 - g0) / 1e9])
    out = [["all_gaps:" + k, v] for k, v in totals.items()]
    singles.sort(key=lambda kv: -kv[1])
    return (out + singles)[:10]


def main() -> None:
    trace_dir, out_path = sys.argv[1], sys.argv[2]
    path = find_xplane(trace_dir)
    ex = extract(path)
    ex["xplane_bytes"] = os.path.getsize(path)
    with open(out_path + ".tmp", "w") as f:
        json.dump(ex, f)
    os.replace(out_path + ".tmp", out_path)


if __name__ == "__main__":
    main()
