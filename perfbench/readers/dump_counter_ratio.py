"""Counters of the stop dumps' `counters` summed over EVERY node, over
others summed alike: the whole run's, as the counters run from a node's
start. params: {"num": key or [keys], "den": key or [keys]}. Nothing from
a program that writes no such dump or counter, or where the denominator
is 0."""

import json
import os
import re

from harness import artifacts


def names(keys) -> list:
    return [keys] if isinstance(keys, str) else list(keys)


def read(obs, params, device):
    if not artifacts.program_keeps_records():
        return None
    run = artifacts.run_dir(obs)
    tops, bottoms = names(params["num"]), names(params["den"])
    num = den = 0.0
    for d in os.listdir(run):
        m = re.fullmatch(r"node(\d+)", d)
        if not m:
            continue
        with open(artifacts.stop_dump(run, int(m.group(1)))) as f:
            counters = json.load(f).get("counters") or {}
        if any(k not in counters for k in tops + bottoms):
            return None
        num += sum(float(counters[k]) for k in tops)
        den += sum(float(counters[k]) for k in bottoms)
    if den <= 0:
        return None
    return num / den
