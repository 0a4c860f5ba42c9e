"""Closed loop: ``clients`` callers, each sending its next request when its
last one returns (after ``think_s``), from window offset 0.

Parameters: ``clients``, ``prompt`` and ``output`` (length distributions),
optional ``think_s`` (default 0) and ``functions``.
"""
from __future__ import annotations

import numpy as np

from bench.gen import BLOCK, Arrival, Reactive, stratified

POOL = BLOCK * 256         # more requests than any window sends


class ClosedLoop(Reactive):
    def __init__(self, params: dict, functions: list,
                 rng: np.random.Generator, seconds: float):
        super().__init__()
        self.fns = params.get("functions") or functions
        self.think = float(params.get("think_s", 0.0))
        self.seconds = seconds
        self.prompts = stratified(params["prompt"], POOL, rng, BLOCK)
        self.outs = stratified(params["output"], POOL, rng, BLOCK)
        self.sent = 0
        for c in range(int(params["clients"])):
            self._send(c, 0.0)

    def _send(self, client: int, t: float) -> None:
        i = self.sent
        self.sent += 1
        self.push(Arrival(t, self.fns[client % len(self.fns)],
                          self.prompts[i % POOL], self.outs[i % POOL],
                          client=client))

    def on_done(self, arrival: Arrival, t: float) -> None:
        nxt = t + self.think
        if nxt < self.seconds:
            self._send(arrival.client, nxt)


def make(params: dict, functions: list, rng: np.random.Generator,
         seconds: float) -> ClosedLoop:
    return ClosedLoop(params, functions, rng, seconds)
