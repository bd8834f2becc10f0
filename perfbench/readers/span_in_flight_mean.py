"""The mean of `in_flight_at_recv` over the daemon's records of the
window: how many other requests were between their t_recv0 and their
t_replied when a request arrived (the queue a call meets). Nothing from
a program that keeps no records, or where the window holds no call."""

from harness import artifacts


def read(obs, params, device):
    records = artifacts.window_records(obs)
    if not records:
        return None
    return sum(r["in_flight_at_recv"] for r in records) / len(records)
