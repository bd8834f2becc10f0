"""A percentile over node 0's heights of the window of a sum of named
parts of the height's trace, ms, from the node's flight-recorder dump
with reason `stop` (`consensus_traces`, as the consensus_trace RPC serves
them). params: {"aux": [keys of the trace's aux notes]} or {"segments":
[names of its segments]}, "q": 0..100. A part a height does not have
counts 0. Nothing from a program that writes no such dump, or where the
window holds no height."""

from harness import artifacts
from harness.observe import quantile


def read(obs, params, device):
    heights = artifacts.window_heights(obs)
    if not heights:
        return None
    group = "aux" if "aux" in params else "segments"
    xs = [1000.0 * sum(float(t.get(group, {}).get(k, 0.0)) for k in params[group])
          for t in heights]
    return quantile(xs, float(params["q"]) / 100.0)
