"""A percentile of one phase of the daemon's own per-call records
(`devd.spans.jsonl`, written when the daemon stops), ms. params:
{"phase": decode | marshal | dispatch | device_wait | reply, "q": 0..100,
"width": the padded bucket whose calls are read (wider batches would blur
a median)}. Nothing from a program that keeps no records, or where the
window holds no call of that width."""

from harness import artifacts
from harness.observe import quantile


def read(obs, params, device):
    records = artifacts.window_records(obs)
    if records is None:
        return None
    xs = [artifacts.phase_ms(r, params["phase"]) for r in records
          if r["width"] == int(params["width"])]
    if not xs:
        return None
    return quantile(xs, float(params["q"]) / 100.0)
