"""A percentile over the rows of a list that the scenario's judge left in
a JSON file in the run's directory. params: {"file": name, "list": key
of the list, "value": key of the number in each row, "q": 0..100}. Rows
without the value are passed over. Nothing where the file is not there (a
program the scenario could not judge so) or no row has the value."""

import json
import os

from harness import artifacts
from harness.observe import quantile


def read(obs, params, device):
    path = os.path.join(artifacts.run_dir(obs), params["file"])
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = json.load(f).get(params["list"]) or []
    xs = [float(r[params["value"]]) for r in rows
          if r.get(params["value"]) is not None]
    if not xs:
        return None
    return quantile(xs, float(params["q"]) / 100.0)
