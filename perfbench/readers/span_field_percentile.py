"""A percentile of one numeric field of the daemon's per-call records
(`devd.spans.jsonl`) over the window's records in which another field is
over 0. params: {"field": name, "where": name of the field that must be
over 0, "q": 0..100, "scale": factor on the field's unit (1e-6 takes
nanoseconds to ms)}. Nothing from a program whose records lack the field
(the parent commit this metric is first measured beside), or where the
window holds no such record."""

from harness import artifacts
from harness.observe import quantile


def read(obs, params, device):
    records = artifacts.window_records(obs)
    if not records or params["field"] not in records[0]:
        return None
    xs = [float(r[params["field"]]) * float(params.get("scale", 1.0))
          for r in records if r.get(params["where"], 0) > 0]
    if not xs:
        return None
    return quantile(xs, float(params["q"]) / 100.0)
