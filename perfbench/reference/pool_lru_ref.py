"""A plain model of the verifier's table pool: two dicts (one of them kept
in order of use, as Python's dicts keep insertion order) and a counter,
no device. Fed the daemon's log of batches in order (every
lane's key, in lane order), it says for every lane how it must have been
served and which key each eviction took:

- a key is SEEN once for every batch it appears in;
- a lane rides its key's table if the key is resident when the batch
  arrives or has now been seen `min_sight` times; else it is a first
  sight and rides the ladder (its key stays out of the pool);
- of the lanes that ride tables, in lane order: a resident key is a hit
  and becomes the most recently used; a key not resident takes a free
  slot or, where there is none, the slot of the least recently used key
  that no earlier lane of this same batch touched, and is built (rebuilt,
  if it was ever evicted); every later lane of that key in the batch is
  served by that build too, and like any lane makes its key the most
  recently used;
- a batch that needs more keys than the pool has slots rides the ladder
  whole (what it evicted before it found that out stays evicted).

`slots` is the number of keys the pool can hold (the program keeps one of
its slots for padding).
"""

from __future__ import annotations

ROUTES = ("malformed", "hit", "first_sight", "built", "rebuilt", "undecodable")


class PoolModel:
    def __init__(self, slots: int, min_sight: int):
        self.slots, self.min_sight = slots, min_sight
        self.seen: dict = {}
        self.order: dict = {}          # resident keys, least recent first
        self.evicted_once: set = set()
        self.counts = {name: 0 for name in ROUTES}
        self.evictions = 0
        self.resident_max = 0

    def batch(self, keys: list, skip: set = frozenset()) -> tuple[list, list]:
        """(route of every lane, keys evicted in order). `keys[i]` None:
        a lane with no key (malformed); lanes whose index is in `skip`
        are outside the model (a key that is no curve point)."""
        present = {k for k in keys if k is not None}
        for k in present:
            self.seen[k] = self.seen.get(k, 0) + 1
        resident = set(self.order)     # as the batch arrives
        routes: list = [None] * len(keys)
        touched: set = set()
        built_here: dict = {}
        evicted: list = []
        for i, k in enumerate(keys):
            if k is None:
                routes[i] = "malformed"
            elif k not in resident and self.seen[k] < self.min_sight:
                routes[i] = "first_sight"
            elif i in skip:
                routes[i] = "undecodable"
        for i, k in enumerate(keys):
            if routes[i] is not None:
                continue
            if k in built_here:
                del self.order[k]
                self.order[k] = True
                routes[i] = built_here[k]
            elif k in self.order:
                del self.order[k]
                self.order[k] = True
                routes[i] = "hit"
            else:
                if len(self.order) >= self.slots:
                    victim = next((v for v in self.order if v not in touched),
                                  None)
                    if victim is None:
                        # more keys than slots: the batch rides the ladder
                        # whole, the slots it took are given back, and
                        # what it evicted on the way stays evicted
                        for taken in built_here:
                            del self.order[taken]
                        routes = ["malformed" if key is None else "first_sight"
                                  for key in keys]
                        break
                    del self.order[victim]
                    self.evicted_once.add(victim)
                    evicted.append(victim)
                    self.evictions += 1
                self.order[k] = True
                routes[i] = built_here[k] = \
                    "rebuilt" if k in self.evicted_once else "built"
            touched.add(k)
        for r in routes:
            self.counts[r] += 1
        self.resident_max = max(self.resident_max, len(self.order))
        return routes, evicted


def replay(header: dict, batches: list[dict]) -> dict:
    """The daemon's log against the model. `header["keys"]`: the keys by
    number (from 1); a batch {"k": key numbers by lane (0: none), "r":
    the route the program took for each lane (an index into the
    header's `routes`), "e": key numbers evicted, in order}. Returns the
    counts the comparisons need."""
    names = header["routes"]
    model = PoolModel(int(header["usable_slots"]), int(header["min_sight"]))
    unlike = wrong_victims = over = 0
    for b in batches:
        keys = [None if n == 0 else n for n in b["k"]]
        took = [names[int(c)] for c in b["r"]]
        skip = {i for i, t in enumerate(took) if t == "undecodable"}
        routes, evicted = model.batch(keys, skip)
        unlike += sum(1 for want, got in zip(routes, took) if want != got)
        wrong_victims += sum(1 for a, g in zip(evicted, b["e"]) if a != g) \
            + abs(len(evicted) - len(b["e"]))
        over += len(model.order) > model.slots
    return {"lanes_routed_unlike_reference": unlike + wrong_victims,
            "resident_over_capacity": over, "counts": dict(model.counts),
            "evictions": model.evictions, "resident": len(model.order),
            "resident_max": model.resident_max}
