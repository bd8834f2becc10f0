"""Shared JAX persistent-compile-cache setup.

Every entry point that compiles — the device daemon, the CLI's
direct-kernel path, __graft_entry__.py, tests/conftest.py —
calls enable() before its first jit. The ed25519 ladder takes ~45s to
compile on the CPU backend and the Pallas ladder minutes for the chip;
caching them is the difference between a 10-minute and a 10-second
start.

Where the cache lives is the OUTSIDE's choice: with
JAX_COMPILATION_CACHE_DIR set, JAX reads the variable itself and this
module sets no directory in code (a machine that is thrown away after
each command can then be handed a cache that survives it). Without the
variable the cache sits at a FIXED path under the checkout — the path
is part of the cache key's surroundings, so a directory that moves
never hits.
"""

from __future__ import annotations

import os

# repo-root/.jax_cache (this file lives at repo-root/tendermint_tpu/)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def _machine_tag() -> str:
    """Stable per-machine cache key from the CPU feature flags — a fixed
    function of the machine, never of the process or the time. XLA:CPU
    AOT artifacts bake in the compile machine's features; loading them on
    a different host spews cpu_aot_loader feature-mismatch errors (and
    risks SIGILL). Scoping the default cache dir by feature-set keeps
    every machine's artifacts separate."""
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return hashlib.sha1(line.encode()).hexdigest()[:12]
    except OSError:
        pass
    import platform

    return platform.machine() or "unknown"


def enable() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use (what the daemon's status reports)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(_DEFAULT_DIR, _machine_tag()),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(jax.config.jax_compilation_cache_dir)
