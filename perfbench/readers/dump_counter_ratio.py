"""One counter of the stop dumps' `counters` summed over EVERY node, over
another summed alike: the whole run's, as the counters run from a node's
start. params: {"num": key, "den": key}. Nothing from a program that
writes no such dump or counter, or where the denominator is 0."""

import json
import os
import re

from harness import artifacts


def read(obs, params, device):
    if not artifacts.program_keeps_records():
        return None
    run = artifacts.run_dir(obs)
    num = den = 0.0
    for d in os.listdir(run):
        m = re.fullmatch(r"node(\d+)", d)
        if not m:
            continue
        with open(artifacts.stop_dump(run, int(m.group(1)))) as f:
            counters = json.load(f).get("counters") or {}
        if params["num"] not in counters or params["den"] not in counters:
            return None
        num += float(counters[params["num"]])
        den += float(counters[params["den"]])
    if den <= 0:
        return None
    return num / den
