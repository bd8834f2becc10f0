"""A percentile of a named series (linear interpolation). params:
{"series": name, "q": 0..100}. Nothing where the series is empty."""


from harness.observe import quantile


def read(obs, params, device):
    xs = obs.series.get(params["series"]) or []
    if not xs:
        return None
    return quantile(xs, float(params["q"]) / 100.0)
