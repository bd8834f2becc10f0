"""Seconds a counter (or several) accumulated during the window, as a
share of the window. params: {"counters": [names]}. Percent."""


def read(obs, params, device):
    ds = [obs.delta(n) for n in params["counters"]]
    if any(v is None for v in ds) or obs.window_s <= 0:
        return None
    return 100.0 * sum(ds) / obs.window_s
