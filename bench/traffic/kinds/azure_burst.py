"""Azure-Functions-like mix (Shahrad et al., "Serverless in the Wild",
ATC'20): a timer group whose functions all fire in unison every
``period_s``, on top of hot functions with Poisson arrivals.

Parameters: ``timer`` = {``functions``, ``period_s``, ``phase_s``},
``hot`` = [{``function``, ``rate``}], ``prompt`` and ``output`` (length
distributions shared by all requests).
"""
from __future__ import annotations

import numpy as np

from bench.gen import Arrival, Scheduled, poisson_times, stratified


def make(params: dict, functions: list, rng: np.random.Generator,
         seconds: float) -> Scheduled:
    timer = params["timer"]
    due = []
    t = float(timer["phase_s"])
    while t < seconds:
        due.extend((t, fn) for fn in timer["functions"])
        t += float(timer["period_s"])
    for hot in params["hot"]:
        due.extend((t, hot["function"])
                   for t in poisson_times(float(hot["rate"]), seconds, rng))
    due.sort()
    prompts = stratified(params["prompt"], len(due), rng)
    outs = stratified(params["output"], len(due), rng)
    return Scheduled([Arrival(t, fn, prompts[i], outs[i])
                      for i, (t, fn) in enumerate(due)])
