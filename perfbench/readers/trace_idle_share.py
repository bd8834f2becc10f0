"""1 - union of device-op intervals over the traced window, percent."""

from harness import trace_reduce


def read(obs, params, device):
    tr = obs.trace
    if not tr:
        return None
    r = trace_reduce.reduce(tr["extracted"])
    if not r.get("devices") or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
