"""Serving launcher: a Dirigent cluster fronting real model replicas.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --requests 32 [--rate 5] [--hedge 0.5] [--slots 4]

Stands up the full orchestration stack in live mode (control plane, data
planes, workers), registers the model as a Function, drives an open-loop
request stream of prompts through the front-end LB, and reports per-request
latency + autoscaling/cold-start behaviour. This is the paper's serving path
with real JAX compute in the sandboxes.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import Cluster, Function, ScalingConfig
from repro.serving.engine import Replica
from repro.simcore import Environment


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="smollm-360m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="virtual-time requests/s")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--hedge", type=float, default=None,
                    help="straggler hedge timeout (s), None = off")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch).reduced(
        n_layers=4, d_model=128, n_heads=4, d_ff=256, vocab=1024)
    replicas = {}

    def create_replica(sandbox):
        rep = Replica(cfg, max_seq=128, rng_seed=args.seed)
        rep.generate([1], max_new_tokens=1)      # compile warm-up
        replicas[sandbox.sandbox_id] = rep

    env = Environment(seed=args.seed)
    cluster = Cluster(env, n_workers=args.workers, runtime="firecracker",
                      create_hook=create_replica, hedge_after=args.hedge)
    cluster.start()
    cluster.register_sync(Function(
        name=cfg.name, image_url=f"registry://{cfg.name}", port=9000,
        scaling=ScalingConfig(target_concurrency=1, stable_window=120,
                              scale_to_zero_grace=120)))
    print(f"[serve] {cfg.name} registered; {args.workers} workers")

    rng = np.random.default_rng(args.seed)
    invs = []
    t_wall = time.perf_counter()

    def driver(env):
        for i in range(args.requests):
            prompt = rng.integers(1, cfg.vocab, size=rng.integers(2, 8)).tolist()

            def payload(p=prompt, i=i):
                rep = next(iter(replicas.values()))
                return rep.generate(p, max_new_tokens=args.max_new, seed=i)

            invs.append(cluster.invoke(cfg.name, exec_time=0.05,
                                       payload=payload))
            yield env.timeout(1.0 / args.rate)

    env.process(driver(env), name="driver")
    env.run(until=args.requests / args.rate + 60.0)
    wall = time.perf_counter() - t_wall

    ok = [i for i in invs if not i.failed and i.t_done > 0]
    lats = np.array([i.e2e_latency for i in ok])
    cold = sum(1 for i in ok if i.cold)
    toks = sum(len(i.result) for i in ok if i.result)
    print(f"[serve] {len(ok)}/{len(invs)} ok; {cold} cold starts; "
          f"{cluster.collector.sandbox_creations} replicas; {toks} tokens")
    print(f"[serve] e2e virtual-time: p50 {np.percentile(lats, 50)*1e3:.0f} ms "
          f"p99 {np.percentile(lats, 99)*1e3:.0f} ms; wall {wall:.1f}s")


if __name__ == "__main__":
    main()
