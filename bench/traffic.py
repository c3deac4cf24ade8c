"""The one traffic generator: a mix's data file in, requests out.

A traffic file (``bench/traffic/<mix>.json``) states:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when its previous one is delivered) or ``"open"``
  (arrivals on a schedule at ``rate_per_s``, whether or not earlier
  requests are done);
* ``sizes``: images per request as a block of counts, e.g.
  ``{"1": 14, "2": 7, ...}``.  The stream of sizes repeats that block,
  each repeat shuffled from the seed, so every seed sends the same
  mix of sizes in another order;
* for an open loop, ``gap_block``: arrivals come in blocks of that many
  gaps, the quantiles of the exponential distribution at
  ``rate_per_s`` (a Poisson process's gaps), each block shuffled from
  the seed -- the same gaps for every seed, in another order;
* ``deadline_s`` (or null) and ``max_queue``: the runtime's deadline
  per request and its admission bound.

Every request also gets its own noise seed, below 2**31 (the serving
engine folds it into a 32-bit PRNG key).
"""
from __future__ import annotations

import dataclasses

import numpy as np

SEED_BOUND = 2**31 - 1


@dataclasses.dataclass
class Planned:
    """One request as the client sends it."""

    rid: int
    n: int                  # images
    seed: int               # the request's noise seed
    due: float              # seconds from the window's start
    client: int = -1        # closed loop: which client sent it


class Stream:
    """Sizes and noise seeds of successive requests, from the seed."""

    def __init__(self, traffic: dict, seed: int):
        self.block = np.repeat(
            np.array([int(k) for k in traffic["sizes"]], np.int64),
            [int(v) for v in traffic["sizes"].values()])
        if self.block.size == 0 or self.block.min() < 1:
            raise ValueError(f"bad size block {traffic['sizes']}")
        self.rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                          int(seed) >> 32, 0x7AFF1C])
        self._sizes: list[int] = []
        self.rid = 0

    def next(self, due: float, client: int = -1) -> Planned:
        if not self._sizes:
            self._sizes = self.rng.permutation(self.block).tolist()[::-1]
        p = Planned(self.rid, int(self._sizes.pop()),
                    int(self.rng.integers(0, SEED_BOUND)), float(due),
                    client)
        self.rid += 1
        return p


def exponential_gaps(rate: float, count: int) -> np.ndarray:
    """The ``count`` midpoint quantiles of Exp(rate), ascending."""
    u = (np.arange(count) + 0.5) / count
    return -np.log1p(-u) / float(rate)


def open_schedule(traffic: dict, seed: int, seconds: float) -> list[Planned]:
    """Every request of an open loop due in ``[0, seconds)``."""
    stream = Stream(traffic, seed)
    gaps = exponential_gaps(float(traffic["rate_per_s"]),
                            int(traffic["gap_block"]))
    out, t = [], 0.0
    while True:
        for g in stream.rng.permutation(gaps):
            t += float(g)
            if t >= seconds:
                return out
            out.append(stream.next(t))

