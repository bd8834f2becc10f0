"""Correctness + sustained speed of the fp32 verify kernels.

Sweeps the 512-lane mixed valid/tampered/malformed correctness check over
BOTH fp32 backends (f32 conv-composed, f32p pallas — the TPU production
default), then measures each one's sustained device rate at batch 8192
with a single aggregate fetch (a sync fetch per batch would time the
dispatch round trip, not the device). ONE process: it owns the device
for its whole run — stop any device daemon first (libtpu gives the chip
to one process)."""

import sys
import time

import numpy as np

sys.path.insert(0, ".")
import jax
from tendermint_tpu.crypto import ed25519 as ed
from tendermint_tpu.ops import ed25519_f32 as F


def main():
    print(jax.devices()[0], file=sys.stderr)
    # correctness: valid + tampered + malformed lanes
    seeds = [bytes([i]) * 32 for i in range(32)]
    pubs = [ed.public_key(s) for s in seeds]
    items, expect = [], []
    for i in range(512):
        k = i % 32
        m = b"msg-%d" % i
        sig = ed.sign(seeds[k], m)
        if i % 7 == 3:
            bad = bytearray(sig); bad[2] ^= 0x40
            items.append((pubs[k], m, bytes(bad))); expect.append(False)
        elif i % 7 == 5:
            items.append((pubs[k], b"other", sig)); expect.append(False)
        elif i % 11 == 1:
            items.append((b"\x00" * 32, m, sig)); expect.append(ed.verify(b"\x00" * 32, m, sig))
        elif i % 13 == 7:
            bad = bytearray(sig); bad[33] ^= 0x80  # tamper s high bits -> s >= L or wrong
            items.append((pubs[k], m, bytes(bad))); expect.append(ed.verify(pubs[k], m, bytes(bad)))
        else:
            items.append((pubs[k], m, sig)); expect.append(True)
    exp = np.array(expect)
    from tendermint_tpu.ops import ed25519_f32p as FP

    for name, mod in (("f32", F), ("f32p", FP)):
        got = mod.verify_batch(items)
        assert (got == exp).all(), f"{name} mismatch at {np.nonzero(got != exp)}"
        print(
            f"{name} correctness: 512 mixed lanes OK "
            f"({exp.sum()} valid, {(~exp).sum()} invalid)"
        )

    # sustained speed, device-resident
    import jax.numpy as jnp

    B = 8192
    items = []
    for i in range(B):
        k = i % 32
        m = b"m%d" % i
        items.append((pubs[k], m, ed.sign(seeds[k], m)))
    prep = F.prepare_batch8(items, B)
    t0 = time.perf_counter()
    F.prepare_batch8(items, B)
    print(f"marshal: {(time.perf_counter()-t0)*1e3:.0f} ms/batch")
    args = tuple(jax.device_put(np.asarray(a)) for a in prep[:6])
    t0 = time.perf_counter()
    ok = np.asarray(F._verify_jit(*args))
    print(f"compile: {time.perf_counter()-t0:.1f} s")
    assert ok.all()
    REPS = 10
    t0 = time.perf_counter()
    outs = [F._verify_jit(*args) for _ in range(REPS)]
    np.asarray(jnp.stack(outs))  # ONE fetch: per-batch syncs time the round trip
    el = (time.perf_counter() - t0) / REPS
    print(f"f32 sustained: {el*1e3:.1f} ms/batch = {B/el:.0f} sigs/s")

    # f32p (pallas ladder): SAME protocol — the production marshal runs
    # ONCE (FP.marshal_device_args, the same helper verify_batch_async
    # uses), then only the device call is timed with one aggregate fetch
    pargs, _valid, _n = FP.marshal_device_args(items)
    fnp = FP._get_verify(FP.S_TILE, FP._interpret())
    okp = np.asarray(fnp(*pargs))
    assert (okp.reshape(-1)[:B] != 0).all()
    t0 = time.perf_counter()
    outs = [fnp(*pargs) for _ in range(REPS)]
    np.asarray(jnp.stack(outs))
    el = (time.perf_counter() - t0) / REPS
    print(f"f32p sustained: {el*1e3:.1f} ms/batch = {B/el:.0f} sigs/s")


if __name__ == "__main__":
    main()
