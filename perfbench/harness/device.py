"""The harness as a client of the daemon: warm the shapes a cell uses,
read the device and the counters, send the probe batch of the output
check. All through the program's own client and socket protocol, the way
a node reaches the daemon."""

from __future__ import annotations

import time

from . import procs


def send(daemon, items, stream_chunk: int | None = None) -> list[bool]:
    """One batch through the socket: streamed in `stream_chunk`-lane
    frames when given (as a node's gateway sends a wide batch), else the
    single-shot op (as it sends a narrow one)."""
    c = daemon.client(io_timeout=600.0)
    try:
        if stream_chunk:
            return [bool(b) for b in c.verify_stream(items, chunk=stream_chunk)]
        return [bool(b) for b in c.verify_batch(items)]
    finally:
        c.close()


def warm_tables(daemon, items, top: int, passes: int,
                stream_chunk: int | None = None) -> dict:
    """Show the daemon every key the window will use, in equal chunks no
    wider than `top` lanes (one table-build program: it compiles per
    count of new keys), `passes` times (2 where first sight rides the
    ladder and the second builds; 1 where TENDERMINT_TPU_COMB_MIN_SIGHT
    is 1). Leaves every key's comb table resident and the verify program
    of the chunks' bucket compiled."""
    per: dict[str, float] = {}
    n_chunks = -(-len(items) // top)
    size = -(-len(items) // n_chunks)
    for k in range(passes):
        t = time.time()
        for i in range(0, len(items), size):
            if not all(send(daemon, items[i:i + size], stream_chunk)):
                raise procs.HarnessError("a warm-up lane was rejected")
        per[f"pass_{k + 1}"] = round(time.time() - t, 3)
    return per


def warm_buckets(daemon, items, buckets: list[int],
                 stream_chunk: int | None = None) -> dict:
    """One batch at every bucket width the window's batches can have."""
    per: dict[str, float] = {}
    for b in sorted(buckets):
        n = min(len(items), b)
        if n <= b // 2 and b > 8:      # not enough lanes to reach this bucket
            continue
        t = time.time()
        if not all(send(daemon, items[:n], stream_chunk)):
            raise procs.HarnessError("a warm-up lane was rejected")
        per[f"bucket_{b}"] = round(time.time() - t, 3)
    return per


def check_device(daemon, rep: dict, chips: int, rehearsal: bool) -> dict:
    """The device as the daemon's JAX reports it. No chip, fewer chips
    than the cell asks for, or a daemon that answered from the host:
    no result."""
    dev = daemon.request("device")
    if dev["platform"] != "tpu" and not rehearsal:
        raise procs.HarnessError(
            f"the daemon's platform is {dev['platform']!r}, not an accelerator")
    if dev["count"] < chips and not rehearsal:
        raise procs.HarnessError(
            f"the cell asks for {chips} chip(s), the daemon holds {dev['count']}")
    if rep.get("error"):
        raise procs.HarnessError(f"the daemon reports {rep['error']}")
    return {k: dev[k] for k in ("platform", "kind", "count")}
