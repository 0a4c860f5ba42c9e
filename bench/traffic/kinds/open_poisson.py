"""Open loop: Poisson arrivals at a fixed rate over the functions named.

Parameters: ``rate`` (requests/s over all functions), ``prompt`` and
``output`` (length distributions, see ``bench.gen.quantile``), optional
``functions`` (default: every function of the configuration, round robin).
"""
from __future__ import annotations

import numpy as np

from bench.gen import Arrival, Scheduled, poisson_times, stratified


def make(params: dict, functions: list, rng: np.random.Generator,
         seconds: float) -> Scheduled:
    fns = params.get("functions") or functions
    times = poisson_times(float(params["rate"]), seconds, rng)
    prompts = stratified(params["prompt"], len(times), rng)
    outs = stratified(params["output"], len(times), rng)
    return Scheduled([Arrival(t, fns[i % len(fns)], prompts[i], outs[i])
                      for i, t in enumerate(times)])
