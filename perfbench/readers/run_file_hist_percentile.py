"""A percentile, ms, of a quantity that every row of a list keeps as a
histogram of counts over shared upper edges in seconds (the last count is
everything above the last edge), the rows' histograms summed: from a JSON
file the scenario's judge left in the run's directory. params: {"file":
name, "list": key of the list, "hist": path of the counts in a row
("link.late_hist"), "edges": key of the edges in the file, "max": path of
a row's largest value, which bounds the open bucket, "q": 0..100}. Inside
a bucket the value is interpolated between its edges. Nothing where the
file is not there, names no edges, or no row has counted anything."""

import json
import os

from harness import artifacts


def _at(row: dict, path: str):
    for key in path.split("."):
        row = (row or {}).get(key)
    return row


def read(obs, params, device):
    path = os.path.join(artifacts.run_dir(obs), params["file"])
    if not os.path.exists(path):
        return None
    with open(path) as f:
        doc = json.load(f)
    edges = [float(e) for e in doc.get(params["edges"]) or []]
    if not edges:
        return None
    total = [0] * (len(edges) + 1)
    largest = 0.0
    for row in doc.get(params["list"]) or []:
        hist = _at(row, params["hist"])
        if not hist:
            continue
        total = [a + int(b) for a, b in zip(total, hist)]
        largest = max(largest, float(_at(row, params["max"]) or 0.0))
    n = sum(total)
    if n == 0:
        return None
    rank = float(params["q"]) / 100.0 * n
    seen = 0
    for i, count in enumerate(total):
        if count and seen + count >= rank:
            lo = edges[i - 1] if i else 0.0
            hi = edges[i] if i < len(edges) else max(largest, lo)
            return 1000.0 * (lo + (hi - lo) * (rank - seen) / count)
        seen += count
    return 1000.0 * largest
