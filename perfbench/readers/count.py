"""How many entries a named series has (a count of events of the window).
params: {"series": name, "needs": name of a scalar that must be > 0 for
the count to mean anything}. A series that is absent reads nothing; one
that is present and empty reads 0."""


def read(obs, params, device):
    if params["series"] not in obs.series:
        return None
    need = params.get("needs")
    if need and not obs.scalars.get(need):
        return None
    return float(len(obs.series[params["series"]]))
