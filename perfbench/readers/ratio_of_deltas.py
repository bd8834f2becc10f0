"""Sum of counter deltas over sum of counter deltas, over the window.
params: {"num": [counter names], "den": [counter names], "scale": x}."""


def read(obs, params, device):
    num = [obs.delta(n) for n in params["num"]]
    den = [obs.delta(n) for n in params["den"]]
    if any(v is None for v in num + den):
        return None
    d = sum(den)
    if d <= 0:
        return None
    return float(params.get("scale", 1.0)) * sum(num) / d
