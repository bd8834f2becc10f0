"""The traffic generator: every seed gets the same multiset of gaps and
the same number of writes, in another order; the cost function and the
peaks table."""

import pytest

from harness import loadgen, peaks, verify_cost


def gaps(offs, seconds):
    return sorted(round(b - a, 9) for a, b in zip(offs, offs[1:] + [seconds]))


def test_same_work_for_every_seed():
    a = loadgen.arrival_offsets(50, 45, 1)
    b = loadgen.arrival_offsets(50, 45, 2**31 + 12345)
    assert len(a) == len(b) == 2250
    assert a != b
    assert gaps(a, 45) == gaps(b, 45)
    assert a[0] == 0.0 and max(a) < 45 and a == sorted(a)
    # exponential: the mean gap is 1/rate, and 1 - 1/e of them are shorter
    g = gaps(a, 45)
    assert abs(sum(g) / len(g) - 1 / 50) < 1e-9
    assert abs(sum(1 for x in g if x < 1 / 50) / len(g) - 0.632) < 0.005


def test_writes_are_signed_and_fresh():
    import os
    from conftest import BENCH
    from reference import kv_ref

    ws = loadgen.make_writes(2**31 + 7, 6, 3, BENCH)
    assert len({w["key"] for w in ws}) == 6
    assert all(kv_ref.tx_valid(w["tx"]) for w in ws)
    assert len({w["tx"][:32] for w in ws}) == 3


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_cost_depends_on_shapes_only():
    assert verify_cost.operations(2000) == 2 * verify_cost.operations(1000)
    assert verify_cost.bytes_moved(10, 150) > verify_cost.bytes_moved(10, 0)
    t, bound = verify_cost.least_seconds(1000, 150, 1000, peaks.peaks_for("TPU v5 lite"))
    assert bound in ("compute", "memory") and 1e-6 < t < 1e-3
