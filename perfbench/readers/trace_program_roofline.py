"""The least time the chip could take for what one of the pool's miss
programs did inside the traced stretch (harness/pool_cost.py, peaks by
device kind) over that program's summed device time there, percent.
params: {"kernel": regex on the trace's program names, "cost": build |
update | ladder}. The work is read from the daemon's records (all of the
run's: the stretch need not lie in the window) that lie
whole inside the stretch (their `keys_built` / `lanes_ladder`): a
program whose call straddles an edge adds time and no work, so the share
errs low, never high. Nothing from a program whose records lack those
fields, or where the stretch holds no such program."""

from harness import artifacts, peaks, pool_cost, trace_reduce

FIELD = {"build": "keys_built", "update": "keys_built", "ladder": "lanes_ladder"}


def read(obs, params, device):
    tr = obs.trace
    if not tr:
        return None
    if not artifacts.program_keeps_records():
        return None
    # every record, not the window's: this cell's stretch lies in set-up
    _header, records = artifacts.load_spans(
        artifacts.spans_path(artifacts.run_dir(obs)))
    field = FIELD[params["cost"]]
    if not records or field not in records[0]:
        return None
    lo, hi = tr["start_wall_ns"], tr["stop_wall_ns"]
    n = sum(r[field] for r in records
            if r["t_recv0"] >= lo and r["t_verdicts"] <= hi)
    if not n:
        return None
    r = trace_reduce.reduce(tr["extracted"], kernel_pattern=params["kernel"])
    if r.get("kernel_s", 0) <= 0:
        return None
    least, _bound = pool_cost.least_seconds(
        params["cost"], n, peaks.peaks_for(device["kind"]))
    return 100.0 * least / r["kernel_s"]
