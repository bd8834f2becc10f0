"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths
compile and execute without TPU hardware. Must run before jax is imported
anywhere in the test process.
"""

import os

# Tests must be hermetic: never route the default Verifier through a
# production device daemon that happens to be serving on this box
# (tendermint_tpu/devd.py) — unconditionally, since the operator may have
# TENDERMINT_DEVD_SOCK exported. test_devd.py points at its own socket
# per-test with monkeypatch.
os.environ["TENDERMINT_DEVD_SOCK"] = "/nonexistent/devd.sock"
# Platform resolution (ops/gateway.resolve_platform): a process that is
# not the device daemon is TOLD its platform; tests are CPU-only, so tell
# them (the env override is consulted first).
os.environ["TENDERMINT_TPU_PLATFORM"] = "cpu"

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin every test process to the CPU backend, whatever JAX_PLATFORMS says.
# libtpu gives a chip (and its lock file) to ONE process: the suite runs
# under several xdist workers, each of which imports jax, so on a machine
# with a chip attached an unpinned worker would take it and every other
# worker's jax.devices() would fail. The chip is chip_smoke.py's business
# (through the device daemon); tests/test_chip_compile.py loads the TPU
# compiler for a DESCRIBED chip inside a fixture, which this pin does not
# affect. The config update happens before any backend is initialized.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The ed25519 ladder takes ~45s/bucket to compile on the CPU backend;
# persist compiled artifacts across test runs.
from tendermint_tpu.jitcache import enable as _enable_jit_cache  # noqa: E402

_enable_jit_cache()


# Round 12 closed the `cryptography` dependency hole: every transport/
# key primitive is in-repo (crypto/x25519, crypto/chacha20poly1305, pure
# secp256k1), so NO test may ever again skip — or fail collection —
# because a crypto backend is missing. The only sanctioned mentions are
# the explicitly-labeled parity-oracle skips (cross-checks that NEED the
# optional package to have something to compare against).
_ILLEGAL_CRYPTO_SKIPS: list = []


def pytest_runtest_logreport(report):
    if not report.skipped:
        return
    reason = (
        report.longrepr[2]
        if isinstance(report.longrepr, tuple)
        else str(report.longrepr)
    )
    low = reason.lower()
    if ("cryptography" in low or "libcrypto" in low) and \
            "parity oracle" not in low and "oracle" not in low:
        _ILLEGAL_CRYPTO_SKIPS.append((report.nodeid, reason))


def pytest_sessionfinish(session, exitstatus):
    if _ILLEGAL_CRYPTO_SKIPS:
        import pytest as _pytest

        raise _pytest.UsageError(
            "tests skipped for a missing crypto backend — the round-12 "
            "in-repo transport contract forbids this (mark genuine "
            "cross-check skips with 'parity oracle' in the reason): "
            + "; ".join(f"{nid}: {r}" for nid, r in _ILLEGAL_CRYPTO_SKIPS)
        )


def pytest_configure(config):
    """Under xdist's loadfile scheduling, hand files to workers in
    COLLECTION order, not largest-first (xdist >= 3.7 reorders by
    default). tests/test_chip_compile.py is four long, Python-heavy
    compiles: sorted last it lands on a worker that has already run a
    dozen other files and still carries their leftover threads, and
    tracing the unrolled ladder under that GIL contention took 732 s
    instead of 140 s (tier-1 910 s instead of ~5 min). In collection
    order it starts at once on a fresh worker, beside everything else."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    """Deselect slow-marked tests on whole-suite runs (keeps the default
    `pytest tests/` under a minute), but honor an explicit -m expression
    or a test named by node id — unlike an addopts `-m "not slow"`, which
    would silently deselect even a directly requested slow test."""
    if config.option.markexpr:
        return
    if any("::" in a for a in config.args):
        return
    slow = [i for i in items if "slow" in i.keywords]
    if slow:
        config.hook.pytest_deselected(items=slow)
        items[:] = [i for i in items if "slow" not in i.keywords]
