"""Shared pieces of the traffic generators in ``bench/traffic/kinds``.

A mix's schedule is the same in every run: sizes and gaps are quantiles of
the mix's distributions at stratified points, in an order drawn once from
the mix's ``schedule_seed``. ``--seed`` draws only the prompts' token ids
(``bench.harness``), so runs with different seeds do the same work at the
same times and differ by timing noise alone. An open loop sends a fixed
number of requests in the window; a closed loop, whose count follows
completions, draws from blocks of ``BLOCK`` so that any prefix of whole
blocks carries the same multiset of sizes.
"""
from __future__ import annotations

import bisect
import heapq
import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

BLOCK = 64


@dataclass
class Arrival:
    """One request the generator wants sent at window offset ``due``."""

    due: float
    function: str
    prompt_len: int
    max_new: int
    client: int = -1          # closed loop: which client sent it


def quantile(dist: Dict, u: float) -> float:
    """Quantile ``u`` in (0, 1) of a length distribution given as data:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive integer range)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return min(max(x, dist["min"]), dist["max"])
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return min(lo + u * (hi - lo + 1), hi)
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified(dist: Dict, n: int, rng: np.random.Generator,
               block: Optional[int] = None) -> List[int]:
    """``n`` integer lengths: the ``block`` stratified quantiles (default:
    one block of ``n``), each block in its own seeded order."""
    block = block or n
    base = [int(round(quantile(dist, (i + 0.5) / block)))
            for i in range(block)]
    out: List[int] = []
    while len(out) < n:
        out.extend(base[j] for j in rng.permutation(block))
    return out[:n]


def poisson_times(rate: float, seconds: float,
                  rng: np.random.Generator) -> List[float]:
    """``round(rate * seconds)`` arrival offsets in ``[0, seconds)`` whose
    gaps are the stratified quantiles of an exponential distribution, in
    seeded order, scaled so that they span the window exactly."""
    n = max(1, int(round(rate * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    gaps = [gaps[j] for j in rng.permutation(n)]
    scale = seconds / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t * scale)
        t += g
    return out


class Scheduled:
    """A fixed list of arrivals, handed out as their due times pass."""

    def __init__(self, arrivals: List[Arrival]):
        self._pending = sorted(arrivals, key=lambda a: (a.due, a.function))
        self._dues = [a.due for a in self._pending]
        self._next = 0

    def pop_due(self, t: float) -> List[Arrival]:
        end = bisect.bisect_right(self._dues, t, lo=self._next)
        out = self._pending[self._next:end]
        self._next = end
        return out

    def next_due(self) -> float:
        if self._next < len(self._pending):
            return self._dues[self._next]
        return math.inf

    def on_done(self, arrival: Arrival, t: float) -> None:
        pass


class Reactive:
    """Arrivals that completions create (closed loops): a heap of due
    requests, fed by ``push``."""

    def __init__(self):
        self._heap: List[tuple] = []
        self._seq = 0

    def push(self, arrival: Arrival) -> None:
        heapq.heappush(self._heap, (arrival.due, self._seq, arrival))
        self._seq += 1

    def pop_due(self, t: float) -> List[Arrival]:
        out = []
        while self._heap and self._heap[0][0] <= t:
            out.append(heapq.heappop(self._heap)[2])
        return out

    def next_due(self) -> float:
        return self._heap[0][0] if self._heap else math.inf

    def on_done(self, arrival: Arrival, t: float) -> Optional[Arrival]:
        raise NotImplementedError
