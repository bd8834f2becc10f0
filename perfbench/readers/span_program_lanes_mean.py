"""The mean lanes of a device program over the window, from the daemon's
records: one program a leading record (`program` == `seq`: a request that
ran alone, or the first of those the daemon merged), at its
`program_lanes`. Nothing from a program whose records do not say which
program a request rode (the parent commit this metric is first measured
beside), or where the window holds no call."""

from harness import artifacts


def read(obs, params, device):
    records = artifacts.window_records(obs)
    if not records or "program_lanes" not in records[0]:
        return None
    lanes = [r["program_lanes"] for r in records if r["program"] == r["seq"]]
    if not lanes:
        return None
    return sum(lanes) / len(lanes)
