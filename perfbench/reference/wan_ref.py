"""Plain reference of a validator net whose links have constant delays:
from the regions, the round-trip times between them and the number of
validators alone, (a) every directed link's one-way delay and round
trip, and (b) the earliest instant, counted from the proposer's entry
into `propose`, at which an observer can hold more than two thirds of
the precommits of that height. Nothing of the program; `itertools`
alone.

Validator i is in region i mod len(regions). A frame crosses a link in
half the round trip between its ends' regions, each way, and in no less.
The floor lets every message take the SHORTEST path through the mesh
(gossip relays proposals, parts and votes, and a table from ping
measurements need not obey the triangle inequality), so no run of a
correct program can come in under it, whatever it relays.

    the proposal reaches i at              P(i)  = d(p, i)
    i holds > 2/3 of the prevotes at       Q1(i) = k-th smallest over j
                                                   of P(j) + d(j, i)
    i holds > 2/3 of the precommits at     Q2(i) = k-th smallest over j
                                                   of Q1(j) + d(j, i)

with d(i, i) = 0, equal voting power, and k the smallest count whose
share is MORE than two thirds: 3k > 2n (11 of 16, 11 of 15, 12 of 17).
A validator prevotes when it has the proposal and precommits when it has
the prevotes; it can do neither sooner. `quorum_floor_ms(p, o)` is Q2(o).
"""

from __future__ import annotations

import itertools


def quorum_count(n: int) -> int:
    """The smallest number of equal validators that is more than two
    thirds of n."""
    return (2 * n) // 3 + 1


class WanNet:
    def __init__(self, regions: list[str], rtt_ms: dict, n: int):
        """`rtt_ms`: {"a:b": ms} with each unordered pair once, a region
        with itself included."""
        self.regions = list(regions)
        self.n = int(n)
        self.rtt = {}
        for pair, ms in rtt_ms.items():
            a, b = pair.split(":")
            for key in ((a, b), (b, a)):
                if self.rtt.get(key, float(ms)) != float(ms):
                    raise ValueError(f"{a}:{b} has two round-trip times")
                self.rtt[key] = float(ms)
        for a, b in itertools.product(self.regions, repeat=2):
            if (a, b) not in self.rtt:
                raise ValueError(f"no round-trip time for {a}:{b}")
        # shortest one-way path between validators, ms (Floyd-Warshall
        # over the full mesh)
        m = self.n
        d = [[0.0 if i == j else self.link_one_way_ms(i, j) for j in range(m)]
             for i in range(m)]
        for k, i, j in itertools.product(range(m), repeat=3):
            if d[i][k] + d[k][j] < d[i][j]:
                d[i][j] = d[i][k] + d[k][j]
        self._d = d

    def region_of(self, i: int) -> str:
        return self.regions[i % len(self.regions)]

    # -- (a) the links -------------------------------------------------------

    def link_rtt_ms(self, i: int, j: int) -> float:
        """The configured round trip of the link between validators i
        and j (two of one region: that region's own)."""
        return self.rtt[(self.region_of(i), self.region_of(j))]

    def link_one_way_ms(self, i: int, j: int) -> float:
        return self.link_rtt_ms(i, j) / 2.0

    def links(self) -> list[tuple[int, int, float, float]]:
        """Every directed link: (from, to, one-way ms, round-trip ms)."""
        return [(i, j, self.link_one_way_ms(i, j), self.link_rtt_ms(i, j))
                for i in range(self.n) for j in range(self.n) if i != j]

    # -- (b) the floor -------------------------------------------------------

    def _kth_arrival(self, cast_at: list[float], i: int) -> float:
        k = quorum_count(self.n)
        return sorted(cast_at[j] + self._d[j][i] for j in range(self.n))[k - 1]

    def prevote_quorum_ms(self, proposer: int) -> list[float]:
        has_proposal = [self._d[proposer][i] for i in range(self.n)]
        return [self._kth_arrival(has_proposal, i) for i in range(self.n)]

    def quorum_floor_ms(self, proposer: int, observer: int) -> float:
        """No sooner than this after `proposer` entered `propose` does
        `observer` hold more than two thirds of the precommits."""
        return self._kth_arrival(self.prevote_quorum_ms(proposer), observer)
