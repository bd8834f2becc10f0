"""Aux notes summed over EVERY node's heights of the window, over other
aux notes summed alike or over the machine's core-seconds, percent.
params: {"num": [keys], "den": [keys]} or {"num": [keys], "den_cores": n}
(n cores for the window's length: the share of the machine that the fleet
of node processes burnt). Nothing from a program whose heights carry none
of the `num` keys, or where the denominator is 0."""

from harness import fleet_dumps


def read(obs, params, device):
    by_node = fleet_dumps.window_heights_by_node(obs)
    if not by_node:
        return None
    heights = [t for hs in by_node.values() for t in hs]
    if not any(k in t.get("aux", {}) for t in heights for k in params["num"]):
        return None

    def total(keys):
        return sum(float(t.get("aux", {}).get(k, 0.0))
                   for t in heights for k in keys)

    if "den_cores" in params:
        den = float(params["den_cores"]) * obs.window_s
    else:
        den = total(params["den"])
    if den <= 0:
        return None
    return 100.0 * total(params["num"]) / den
