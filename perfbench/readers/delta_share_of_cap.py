"""Bytes a counter moved during the window as a share of what a rate cap
allows: delta / (links x bytes_per_s x window). params: {"counter": name,
"links": scalar name, "bytes_per_s": scalar name}. Percent."""


def read(obs, params, device):
    d = obs.delta(params["counter"])
    links = obs.scalars.get(params["links"])
    rate = obs.scalars.get(params["bytes_per_s"])
    if d is None or not links or not rate or obs.window_s <= 0:
        return None
    return 100.0 * d / (links * rate * obs.window_s)
