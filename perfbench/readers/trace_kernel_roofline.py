"""The least time the chip could take for the lanes verified in the
traced window (harness/verify_cost.py, peaks by device kind) over the
kernel's summed device time, percent. params: {"kernel": regex}."""

from harness import peaks, trace_reduce, verify_cost


def read(obs, params, device):
    tr = obs.trace
    if not tr:
        return None
    lanes = obs.lanes_inside(tr["start_wall_ns"], tr["stop_wall_ns"])
    if not lanes:
        return None
    r = trace_reduce.reduce(tr["extracted"], kernel_pattern=params["kernel"])
    if r.get("kernel_s", 0) <= 0:
        return None
    pk = peaks.peaks_for(device["kind"])
    least, _bound = verify_cost.least_seconds(
        lanes, int(tr.get("message_bytes", 0)),
        int(tr.get("distinct_keys", 0)), pk)
    return 100.0 * least / r["kernel_s"]
