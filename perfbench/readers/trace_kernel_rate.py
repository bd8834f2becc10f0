"""Lanes of the verifier calls that lie inside the traced stretch (the
launcher's spans) over the summed device durations of the kernel's events. params: {"kernel": regex on the
trace's event names}. Nothing where the kernel did not run."""

from harness import trace_reduce


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
    return lanes / r["kernel_s"]
