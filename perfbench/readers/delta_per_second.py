"""What a counter (or several) gained during the window, a second of
the window. params: {"counters": [names]}. Nothing where a counter was
not read."""


def read(obs, params, device):
    ds = [obs.delta(n) for n in params["counters"]]
    if any(v is None for v in ds) or obs.window_s <= 0:
        return None
    return sum(ds) / obs.window_s
