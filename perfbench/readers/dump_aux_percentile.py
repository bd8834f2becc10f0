"""A percentile over one node's heights of the window of a sum of aux
notes of the height's trace, ms (the notes are seconds), from the node's
stop dump. params: {"aux": [keys], "q": 0..100, "node": index}. Unlike
`dump_height_percentile`, a program whose heights carry none of the keys
(the parent commit a new note is first measured beside) reads nothing,
not 0."""

from harness import fleet_dumps
from harness.observe import quantile


def read(obs, params, device):
    by_node = fleet_dumps.window_heights_by_node(obs)
    heights = (by_node or {}).get(int(params.get("node", 0)))
    if not heights:
        return None
    keys = params["aux"]
    if not any(k in t.get("aux", {}) for t in heights for k in keys):
        return None
    xs = [1000.0 * sum(float(t.get("aux", {}).get(k, 0.0)) for k in keys)
          for t in heights]
    return quantile(xs, float(params["q"]) / 100.0)
