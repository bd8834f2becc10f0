"""A percentile, ms, over one node's heights of the window that carry an
aux note (seconds) of the height's trace, from the node's stop dump.
params: {"aux": key, "q": 0..100, "node": index}. A height without the
note is left out, not counted 0 (the note is made only where there was
something to time: `apply_verify_s` and `apply_app_s` for a block that
held txs). Nothing from a program whose heights carry no such note (the
parent commit a new note is first measured beside)."""

from harness import fleet_dumps
from harness.observe import quantile


def read(obs, params, device):
    by_node = fleet_dumps.window_heights_by_node(obs)
    heights = (by_node or {}).get(int(params.get("node", 0)))
    key = params["aux"]
    xs = [1000.0 * float(t["aux"][key]) for t in heights or ()
          if key in (t.get("aux") or {})]
    if not xs:
        return None
    return quantile(xs, float(params["q"]) / 100.0)
