"""The lanes the comb program served inside the traced stretch over that
program's summed device time there: as a rate (params "as": "rate",
lanes a second) or as the share of the roofline `trace_kernel_roofline`
reads ("as": "roofline_share", percent; harness/verify_cost.py, peaks by
device kind). params: {"kernel": regex on the trace's program names,
"as"}.

`trace_kernel_rate` counts every lane of the calls inside the stretch.
Where a pool sends lanes to the first-sight ladder that is too many: the
ladder's lanes ran another program (`jit__verify_impl`), and this cell's
stretch holds a call that is nothing but ladder. Here the lanes are the
daemon's records' (all of the run's: the stretch need not lie in the
window), one program a leading record (`program` == `seq`), its
`program_lanes` less its `lanes_ladder`, of the records that lie whole
inside the stretch. Nothing from a program whose records lack those
fields, or where the comb program did not run."""

from harness import artifacts, peaks, trace_reduce, verify_cost


def read(obs, params, device):
    tr = obs.trace
    if not tr or not artifacts.program_keeps_records():
        return None
    _header, records = artifacts.load_spans(
        artifacts.spans_path(artifacts.run_dir(obs)))
    if not records or "lanes_ladder" not in records[0]:
        return None
    lo, hi = tr["start_wall_ns"], tr["stop_wall_ns"]
    lanes = sum(r["program_lanes"] - r["lanes_ladder"] for r in records
                if r["program"] == r["seq"]
                and r["t_recv0"] >= lo and r["t_verdicts"] <= hi)
    r = trace_reduce.reduce(tr["extracted"], kernel_pattern=params["kernel"])
    if lanes <= 0 or r.get("kernel_s", 0) <= 0:
        return None
    if params["as"] == "rate":
        return lanes / r["kernel_s"]
    least, _bound = verify_cost.least_seconds(
        lanes, int(tr.get("message_bytes", 0)),
        int(tr.get("distinct_keys", 0)), peaks.peaks_for(device["kind"]))
    return 100.0 * least / r["kernel_s"]
