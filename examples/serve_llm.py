"""End-to-end serving driver: a real JAX LM served through Dirigent.

This is the paper's serving path with real compute in the sandboxes:
  * each Dirigent *sandbox* hosts a ``Replica`` of a (reduced) smollm-360m
    running real jitted decode steps on this machine, managed by the
    ``LiveBackend`` (``create_hook`` builds it, ``teardown_hook`` reclaims
    it when the autoscaler scales down);
  * invocations carry ``LiveRequest`` prompts; the DP dispatches each to a
    sandbox and the worker executes it *in that sandbox's* batcher slots —
    concurrent requests share decode steps — billing measured wall time to
    the virtual clock (live mode);
  * cold starts = replica construction; the XLA compile is paid once into
    the shared executable cache, so every replica after the first starts
    warm (serving/exec_cache.py);
  * finally, the ContinuousBatcher is driven directly to show slot-level
    batched decoding (the per-sandbox concurrency throttle).

Run:  PYTHONPATH=src python examples/serve_llm.py
"""
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import Cluster, Function, ScalingConfig
from repro.core.request import LiveRequest
from repro.live import LiveBackend, LiveFunctionSpec
from repro.serving.engine import ContinuousBatcher, Replica
from repro.simcore import Environment


def main() -> None:
    enable_compile_cache()
    cfg = get_config("smollm-360m").reduced(
        n_layers=4, d_model=128, n_heads=4, d_ff=256, vocab=1024)
    probe = Replica(cfg, max_seq=96)
    print(f"model: smollm-360m (reduced) — "
          f"{sum(x.size for x in jax.tree.leaves(probe.params)):,} params")

    backend = LiveBackend(default_spec=LiveFunctionSpec(
        cfg=cfg, mode="process", max_seq=96, max_slots=4,
        default_max_new=8))
    env = Environment(seed=7)
    cluster = Cluster(env, n_workers=4, runtime="firecracker",
                      live_backend=backend, sandbox_concurrency=4)
    cluster.start()
    cluster.register_sync(Function(
        name="llm", image_url="registry://smollm:reduced", port=9000,
        scaling=ScalingConfig(target_concurrency=4)))

    prompts = [[1, 5, 9], [2, 6], [3, 7, 11, 13], [4, 8, 12], [1, 2, 3],
               [9, 9, 9], [5], [6, 10]]
    t_wall = time.perf_counter()
    invs = [cluster.invoke("llm", exec_time=0.05,
                           request=LiveRequest(prompt=p, max_new_tokens=8))
            for p in prompts]
    # live mode bills real wall time to the virtual clock, so run until
    # every invocation has an outcome rather than to a fixed horizon
    while any(i.t_done < 0 for i in invs):
        env.run(until=env.now + 1.0)
    wall = time.perf_counter() - t_wall

    starts = backend.start_log
    print(f"\nserved {sum(1 for i in invs if not i.failed)}/{len(invs)} "
          f"requests through the Dirigent data plane "
          f"({len(starts)} replicas cold-started, "
          f"{sum(1 for s in starts if not s['cold'])} of them warm via the "
          f"shared executable cache); wall {wall:.1f}s")
    for i, inv in enumerate(invs[:4]):
        req = inv.request
        print(f"  req{i}: tokens={req.tokens} "
              f"e2e(virtual)={inv.e2e_latency * 1e3:.0f} ms "
              f"cold={inv.cold} shared_slots_with={req.batched_with}")

    # -- continuous batching inside one replica ------------------------------
    rep = Replica(cfg, max_seq=96)        # warm: executables from the cache
    cb = ContinuousBatcher(rep, max_slots=4)
    rids = [cb.add_request(p, max_new=8) for p in prompts[:4]]
    t0 = time.perf_counter()
    cb.run_until_done()
    dt = time.perf_counter() - t0
    tokens = sum(len(v) for v in cb.finished.values())
    print(f"\ncontinuous batcher: {len(rids)} requests, {tokens} tokens in "
          f"{cb.steps} lockstep decode steps ({tokens / dt:.0f} tok/s wall)")
    # consistency with single-request generation:
    single = rep.generate(prompts[0], max_new_tokens=8)
    assert cb.finished[rids[0]] == single, "batched != single-request output"
    print("batched output == single-request output (exactness check passed)")


if __name__ == "__main__":
    main()
