"""Stamps inside one timed section of a thread: the section's owner opens
a `clock`, and the code it calls, at any depth and in any layer, marks
`stamp(name)` without knowing who times it. Consensus opens one around a
block's apply; the app stamps its whole-block signature call
(`apply_verify`), the executor the app's fold and Commit (`apply_app`).
Outside a clock a stamp is one attribute test and does nothing."""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

_tls = threading.local()


def stamp(name: str) -> None:
    """Stamp `name` on the section this thread runs, now; the first stamp
    of a name wins."""
    stamps = getattr(_tls, "stamps", None)
    if stamps is not None and name not in stamps:
        stamps[name] = time.monotonic()


@contextmanager
def clock():
    """Around one section: yields the dict its stamps land in, with the
    section's `start`."""
    stamps = {"start": time.monotonic()}
    _tls.stamps = stamps
    try:
        yield stamps
    finally:
        _tls.stamps = None
