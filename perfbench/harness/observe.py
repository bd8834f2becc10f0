"""What a scenario hands the per-layer readers: named series, counter
pairs (at the window's open and close), scalars, the launcher's compile
stamps and verifier spans, and the reduced device trace of a traced run.
Readers take what they need by name and return nothing where it is not
there."""

from __future__ import annotations

import time
from dataclasses import dataclass, field


def quantile(xs: list[float], q: float) -> float:
    """Linear interpolation between order statistics, q in [0, 1]."""
    s = sorted(xs)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def sleep_until(wall: float) -> None:
    while True:
        d = wall - time.time()
        if d <= 0:
            return
        time.sleep(min(d, 0.2))


@dataclass
class Observations:
    window_s: float
    open_wall: float
    series: dict = field(default_factory=dict)     # name -> [float]
    counters: dict = field(default_factory=dict)   # name -> (open, close)
    scalars: dict = field(default_factory=dict)    # name -> float
    compiles_in_window: list = field(default_factory=list)
    spans: list = field(default_factory=list)      # (start_ns, end_ns, lanes)
    trace: dict | None = None                      # trace_reduce.reduce(...)

    def set_launcher(self, snap: dict, open_wall: float, close_wall: float) -> None:
        lo, hi = int(open_wall * 1e9), int(close_wall * 1e9)
        # a compile that ENDED inside the window compiled inside it
        self.compiles_in_window = [c for c in snap.get("compiles", [])
                                   if lo <= c[0] <= hi]
        self.spans = [tuple(s) for s in snap.get("spans", [])]

    def lanes_histogram(self) -> dict:
        """How many verifier calls of the window had how many lanes."""
        lo = int(self.open_wall * 1e9)
        hi = lo + int(self.window_s * 1e9)
        out: dict[str, int] = {}
        for s0, _s1, lanes in self.spans:
            if lo <= s0 <= hi:
                out[str(lanes)] = out.get(str(lanes), 0) + 1
        return dict(sorted(out.items(), key=lambda kv: int(kv[0])))

    def lanes_inside(self, t0_ns: int, t1_ns: int) -> int:
        """Lanes of the verifier calls that began and ended inside a
        stretch of wall clock (the traced one)."""
        return sum(n for s0, s1, n in self.spans if s0 >= t0_ns and s1 <= t1_ns)

    def delta(self, name: str):
        pair = self.counters.get(name)
        if pair is None:
            return None
        return float(pair[1]) - float(pair[0])
