"""The mean of a named series. params: {"series": name}. Nothing where
the series is empty."""


def read(obs, params, device):
    xs = obs.series.get(params["series"]) or []
    if not xs:
        return None
    return sum(xs) / len(xs)
