"""1 - the device's busy time over the WHOLE window, percent: every
verifier call of the window (the launcher's spans) at the device time the
traced stretch read for a call of its width (harness/trace_reduce.py:
window_busy). For a cell whose trace can hold only a fraction of a second.
Nothing without a trace, or where the trace and the spans share no call."""

from harness import trace_reduce


def read(obs, params, device):
    tr = obs.trace
    if not tr or "widths" not in tr:
        return None
    lo = int(obs.open_wall * 1e9)
    r = trace_reduce.window_busy(tr["extracted"], obs.spans, lo,
                                 lo + int(obs.window_s * 1e9), tr["widths"])
    if r is None or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
