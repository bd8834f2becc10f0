"""A percentile, ms, over one node's heights of the window of the time
between two arrival marks of the height's trace (wall-clock instants),
from the node's stop dump. params: {"from": mark, "to": mark, "q":
0..100, "node": index, "min_aux": {key: least value}, "skip": mark}.
Heights that lack either mark, whose aux notes fall short of `min_aux`,
or that carry the `skip` mark (e.g. `propose_as_proposer`: the node made
the proposal itself) are left out. Nothing where no height is left (a
program without the `to` mark)."""

from harness import fleet_dumps
from harness.observe import quantile


def read(obs, params, device):
    by_node = fleet_dumps.window_heights_by_node(obs)
    heights = (by_node or {}).get(int(params.get("node", 0)))
    least = params.get("min_aux", {})
    skip = params.get("skip")
    xs = []
    for t in heights or ():
        arr, aux = t.get("arrivals") or {}, t.get("aux") or {}
        if params["from"] not in arr or params["to"] not in arr \
                or (skip and skip in arr):
            continue
        if any(float(aux.get(k, 0.0)) < v for k, v in least.items()):
            continue
        xs.append(1000.0 * (arr[params["to"]] - arr[params["from"]]))
    if not xs:
        return None
    return quantile(xs, float(params["q"]) / 100.0)
