"""1 - the device's busy time over the WHOLE window, percent, for a cell
whose calls do not all run the same programs. An ESTIMATE from counts and
the traced stretch's program times, not a trace of the window. The comb
program's share is `window_idle_share`'s (every verifier call of the
window at the device time the traced stretch read for a call of its
width, from that program's events alone) less the calls that were ladder
alone (the daemon's records: `ran` == ladder_only; they ran no comb
program); the miss programs are added by count: the window's table
builds, each at the mean device time of a build and of a pool update in
the stretch, and its ladder programs at the mean of a ladder there.
params: {"comb", "build", "update", "ladder": regexes on the trace's
program names}. Nothing without a trace, where the stretch and the spans
share no call, or from a program that counts no builds."""

import re

from harness import artifacts, trace_reduce


def _mean_s(extracted: dict, pattern: str) -> float:
    pat = re.compile(pattern)
    w0, w1 = extracted.get("window") or (None, None)
    durs = [d for dev in extracted.get("devices") or []
            for name, s, d in dev["modules"]
            if pat.search(name) and w0 is not None and w0 <= s <= w1]
    return sum(durs) / len(durs) / 1e9 if durs else 0.0


def read(obs, params, device):
    tr = obs.trace
    builds, ladders = obs.delta("pool.builds"), obs.delta("pool.ladders")
    if not tr or "widths" not in tr or builds is None or ladders is None:
        return None
    ex = tr["extracted"]
    comb = re.compile(params["comb"])
    only_comb = {**ex, "devices": [
        {**dev, "modules": [m for m in dev["modules"] if comb.search(m[0])]}
        for dev in ex.get("devices") or []]}
    lo = int(obs.open_wall * 1e9)
    r = trace_reduce.window_busy(only_comb, obs.spans, lo,
                                 lo + int(obs.window_s * 1e9), tr["widths"])
    if r is None or r["window_s"] <= 0:
        return None
    by_width = {int(w): ms / 1e3 for w, ms in r["device_ms_by_width"].items()}
    no_comb = sum(
        by_width.get(trace_reduce.width_of(rec["program_lanes"], tr["widths"]),
                     min(by_width.values()))
        for rec in artifacts.window_records(obs) or []
        if rec["program"] == rec["seq"] and rec.get("ran") == "ladder_only")
    busy = r["busy_s"] - no_comb \
        + builds * (_mean_s(ex, params["build"]) + _mean_s(ex, params["update"])) \
        + ladders * _mean_s(ex, params["ladder"])
    return 100.0 * (1.0 - busy / r["window_s"])
