"""The general traffic generator: one cell's mix from its data file.

A workload file (``bench/workloads/<cell>.json``) gives the arrival
process and the request sizes; this module turns them and a seed into
requests.  Keys:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last resolves) or ``"poisson"`` (open loop at
  ``rate_per_s``).
* ``sizes``: ``{"min": a, "max": b, "weight": "inverse" | "uniform"}``,
  images per request; ``inverse`` draws n with probability ~ 1/n.
* ``pool_images``: distinct seeded images that requests slice from.
* ``schedule_seed`` (open loop): the multiset of gaps and sizes is drawn
  once from this fixed seed, and every run seed sends the same multiset in
  another order, so seeds differ in timing and content, not in work.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float          # seconds after the window opens
    images: int
    pool_offset: int


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def size_table(sizes: dict):
    """(values, probabilities) of the request-size distribution."""
    lo, hi = int(sizes["min"]), int(sizes["max"])
    vals = np.arange(lo, hi + 1)
    weight = sizes.get("weight", "uniform")
    if weight == "inverse":
        p = 1.0 / vals
    elif weight == "uniform":
        p = np.ones(len(vals))
    else:
        raise ValueError(f"size weight {weight!r}")
    return vals, p / p.sum()


def exact_sizes(sizes: dict, n: int) -> np.ndarray:
    """``n`` sizes whose counts follow the distribution exactly
    (largest-remainder rounding), in ascending order."""
    vals, p = size_table(sizes)
    want = p * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts))[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(vals, counts)


def pool_offsets(wl: dict, sizes: np.ndarray, r: np.random.Generator):
    pool = int(wl["pool_images"])
    return np.array([r.integers(0, pool - int(n) + 1) for n in sizes])


def open_schedule(wl: dict, seconds: float, seed: int,
                  stream: int = 0, rate_per_s: float = None
                  ) -> List[Arrival]:
    """Poisson arrivals over ``seconds``: a fixed multiset of gaps and
    sizes (from ``schedule_seed``), in the order ``seed`` draws."""
    rate = float(rate_per_s if rate_per_s is not None else wl["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(int(wl["schedule_seed"]))
    gaps = base.exponential(1.0 / rate, n + 1)
    gaps *= seconds / gaps.sum()
    sizes = exact_sizes(wl["sizes"], n)
    r = rng(seed, stream)
    gaps = r.permutation(gaps)[:n]
    sizes = r.permutation(sizes)
    offs = pool_offsets(wl, sizes, r)
    t = np.cumsum(gaps)
    return [Arrival(float(a), int(b), int(c))
            for a, b, c in zip(t, sizes, offs)]


class ClosedSource:
    """Request sizes and pool offsets for a closed loop, from the seed."""

    def __init__(self, wl: dict, seed: int, stream: int = 0):
        self.wl = wl
        self.vals, self.p = size_table(wl["sizes"])
        self.r = rng(seed, stream)

    def next(self) -> Arrival:
        n = int(self.r.choice(self.vals, p=self.p))
        off = int(self.r.integers(0, int(self.wl["pool_images"]) - n + 1))
        return Arrival(0.0, n, off)


def flush_sizes(wl: dict, max_batch: int) -> List[int]:
    """Every image count one flush of this traffic can carry.

    A flush holds whole requests; the queue flushes as soon as the
    pending count reaches ``max_batch``, so one request can lift it to
    ``max_batch + max_size - 1``."""
    lo, hi = int(wl["sizes"]["min"]), int(wl["sizes"]["max"])
    reach = {0}
    cap = max_batch + hi - 1
    frontier = {0}
    while frontier:
        nxt = set()
        for s in frontier:
            if s >= max_batch:
                continue
            for n in range(lo, hi + 1):
                if s + n <= cap and s + n not in reach:
                    nxt.add(s + n)
        reach |= nxt
        frontier = nxt
    if wl["loop"] == "closed":
        reach = {s for s in reach if s <= hi * int(wl["clients"])}
    return sorted(reach - {0})


def ticket_slices(wl: dict, n: int):
    """Every (offset, size) at which a flush of ``n`` images can hand a
    ticket its rows: requests of the mix packed back to back."""
    lo, hi = int(wl["sizes"]["min"]), int(wl["sizes"]["max"])
    starts = {0}
    frontier = {0}
    while frontier:
        nxt = set()
        for s in frontier:
            for m in range(lo, hi + 1):
                if s + m < n and s + m not in starts:
                    nxt.add(s + m)
        starts |= nxt
        frontier = nxt
    out = set()
    for s in starts:
        for m in range(lo, hi + 1):
            if s + m <= n and not (s == 0 and m == n):
                # the end must be reachable too: the rest of the flush is
                # whole requests
                if _fits(n - s - m, lo, hi):
                    out.add((s, m))
    return sorted(out)


def _fits(rest: int, lo: int, hi: int) -> bool:
    """Whether ``rest`` images split into requests of lo..hi images."""
    if rest == 0:
        return True
    k_min = -(-rest // hi)
    return k_min * lo <= rest
