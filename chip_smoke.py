"""Smoke run of Dirigent's live serving path on one TPU chip.

Drives the live invoke path once through the entry points a user calls (as
``examples/serve_llm.py`` does): a ``Cluster`` with a process-mode
``LiveBackend`` serves seeded LLM requests, each going front end -> data
plane -> worker -> the sandbox's ``ContinuousBatcher``. The payload is
smollm-360m at its published widths and depth in bf16, with random weights
made from a seed. Then, for two prompts, the served decode executable's last
logits are checked against a plain float32 forward pass of the same params.

    python chip_smoke.py

It exits non-zero, and prints no result, when JAX finds no TPU, when a
request fails or comes back short, or when the reference check fails. The
numbers it prints come from a smoke run, not a benchmark. Its last line is
one JSON object naming the device.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

ARCH = "smollm-360m"
N_REQUESTS = 16
PROMPT_LENS = (32, 128)        # inclusive range of seeded prompt lengths
MAX_NEW = 32
MAX_SEQ = 2048                 # per-slot KV-cache length
MAX_SLOTS = 8                  # batcher slots per sandbox
MAX_REPLICAS = 2               # 16 requests / 8 slots
N_REFERENCE = 2                # prompts checked against the f32 forward
SEED = 0
WALL_LIMIT_S = 900.0
# Relative L2 error of the served bf16 logits against the float32 reference.
# bf16 keeps 8 significant bits (unit roundoff 2^-8 ~ 4e-3); every matmul
# output and the residual stream are rounded to bf16 once per layer, 32
# layers deep, and the errors add roughly as a random walk, so ~1e-2 is the
# expected size. 5e-2 leaves room for that and still fails on a wrong mask,
# position or cache slot, which moves the logits by O(1).
REF_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase of the smoke run produced a wrong or missing result."""


def serve(cfg, *, n_requests: int = N_REQUESTS,
          prompt_lens: tuple = PROMPT_LENS, max_new: int = MAX_NEW,
          max_seq: int = MAX_SEQ, max_slots: int = MAX_SLOTS,
          max_replicas: int = MAX_REPLICAS, n_reference: int = N_REFERENCE,
          seed: int = SEED, tol: float = REF_TOL) -> dict:
    """Serve ``n_requests`` seeded prompts through a live cluster, then check
    ``n_reference`` of them against the float32 reference. Returns what was
    measured; raises ``SmokeFailure`` if any phase went wrong."""
    import jax
    import numpy as np

    from repro.configs.base import ShapeSpec
    from repro.core import Cluster, Function, ScalingConfig
    from repro.core.request import LiveRequest
    from repro.live import LiveBackend, LiveFunctionSpec
    from repro.simcore import Environment

    spec = LiveFunctionSpec(cfg=cfg, max_seq=max_seq, max_slots=max_slots,
                            default_max_new=max_new)
    backend = LiveBackend(default_spec=spec)
    env = Environment(seed=seed)
    cluster = Cluster(env, n_workers=max_replicas, runtime="firecracker",
                      live_backend=backend, sandbox_concurrency=max_slots)
    cluster.start()
    cluster.register_sync(Function(
        name="llm", image_url=f"registry://{cfg.name}", port=9000,
        scaling=ScalingConfig(target_concurrency=max_slots,
                              max_scale=max_replicas)))

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_requests)
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).tolist()
               for n in lens]
    t0 = time.perf_counter()
    invs = [cluster.invoke("llm", exec_time=0.05,
                           request=LiveRequest(prompt=p,
                                               max_new_tokens=max_new))
            for p in prompts]
    # live mode bills real wall time to the virtual clock, so no virtual
    # horizon is safe: run until every invocation has an outcome
    while any(i.t_done < 0 for i in invs):
        if time.perf_counter() - t0 > WALL_LIMIT_S:
            raise SmokeFailure(
                f"{sum(i.t_done < 0 for i in invs)} of {n_requests} "
                f"requests unfinished after {WALL_LIMIT_S:.0f} s")
        env.run(until=env.now + 1.0)
    wall_s = time.perf_counter() - t0
    served_mem = jax.devices()[0].memory_stats() or {}

    bad = [i for i in invs
           if i.failed or len(i.request.tokens or ()) < max_new]
    if bad:
        raise SmokeFailure("; ".join(
            f"request {i.inv_id}: failed={i.failed} "
            f"tokens={len(i.request.tokens or ())} "
            f"reason={i.failure_reason or i.request.failure_reason!r}"
            for i in bad))
    if not backend.sandboxes:
        raise SmokeFailure("no live sandbox left for the reference check")

    sandboxes = list(backend.sandboxes.values())
    entry = backend.exec_cache.get(cfg, sandboxes[0].replica.run_cfg)
    compile_s = entry.shapes[ShapeSpec("live", max_seq, max_slots, "decode")]
    ref = check_reference(sandboxes[0].replica, prompts[:n_reference],
                          max_slots)
    if not ref["max_rel_err"] <= tol:
        raise SmokeFailure(f"served logits are {ref['max_rel_err']:.3e} "
                           f"from the float32 reference (limit {tol:.0e})")
    starts = backend.start_log
    return {
        "requests": len(invs),
        "tokens": sum(len(i.request.tokens) for i in invs),
        "decode_steps": sum(s.batcher.steps for s in sandboxes),
        "wall_s": wall_s,
        "compile_s": compile_s,
        "cold_create_ms": [1e3 * r["wall_s"] for r in starts if r["cold"]],
        "warm_create_ms": [1e3 * r["wall_s"] for r in starts
                           if not r["cold"]],
        "params": sum(int(x.size) for x in
                      jax.tree.leaves(sandboxes[0].replica.params)),
        "peak_bytes_serving": served_mem.get("peak_bytes_in_use"),
        "tol": tol,
        **ref,
    }


def check_reference(replica, prompts, max_slots: int) -> dict:
    """Feed each prompt through the replica's decode executable, with the
    batcher's signature (``max_slots`` rows, one cache length per row), and
    compare each prompt's last logits with a float32 forward pass of the
    same params under the highest matmul precision, using the O(S^2)
    reference attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.api import RunConfig, build_model

    n, longest = len(prompts), max(len(p) for p in prompts)
    cache = replica.new_cache(max_slots)
    served = [None] * n
    for t in range(longest):
        toks = np.zeros((max_slots, 1), np.int32)
        lens = np.zeros((max_slots,), np.int32)
        for r, p in enumerate(prompts):
            toks[r, 0] = p[min(t, len(p) - 1)]
            lens[r] = min(t, len(p))
        logits, cache = replica._decode(
            replica.params, cache,
            {"tokens": jnp.asarray(toks), "cache_len": jnp.asarray(lens)})
        # wait for every step, as the batcher does
        logits = np.asarray(logits[:n], np.float32)
        for r, p in enumerate(prompts):
            if t == len(p) - 1:
                served[r] = logits[r]

    cfg32 = dataclasses.replace(replica.cfg, dtype="float32")
    model32 = build_model(cfg32, RunConfig(attn_impl="reference"))
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), replica.params)
    # causal attention: right-padding leaves each prompt's logits unchanged
    padded = np.zeros((n, longest), np.int32)
    for r, p in enumerate(prompts):
        padded[r, :len(p)] = p
    with jax.default_matmul_precision("highest"):
        full = jax.jit(model32.forward)(params32,
                                        {"tokens": jnp.asarray(padded)})
        full = np.asarray(full, np.float32)
    errs, agree = [], 0
    for r, p in enumerate(prompts):
        want = full[r, len(p) - 1]
        errs.append(float(np.linalg.norm(served[r] - want)
                          / np.linalg.norm(want)))
        agree += int(np.argmax(served[r]) == np.argmax(want))
    return {"max_rel_err": max(errs), "rel_errs": errs,
            "argmax_agree": agree, "n_reference": n}


def main() -> int:
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache()
    cfg = get_config(ARCH)
    print(f"smoke run, not a benchmark: live serving path, {ARCH} at "
          f"published widths ({cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}q/{cfg.n_kv_heads}kv heads, vocab {cfg.vocab}), "
          f"{cfg.dtype}, random weights (seed {SEED})")
    print(f"device: {dev.device_kind}, count {len(devices)}")
    print(f"compile cache: {cache_dir}")
    try:
        out = serve(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    mem = dev.memory_stats() or {}
    print(f"params: {out['params']:,}")
    print(f"memory_stats: bytes_limit={mem.get('bytes_limit')} "
          f"peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
          f"(after serving, before the reference check: "
          f"{out['peak_bytes_serving']})")
    print(f"decode executable ({MAX_SLOTS} slots x {MAX_SEQ}): first call "
          f"{out['compile_s']:.3f} s (compile, or persistent-cache load)")
    print(f"sandbox starts: cold ms {out['cold_create_ms']}, "
          f"warm ms {out['warm_create_ms']}")
    print(f"served {out['requests']}/{N_REQUESTS} requests, "
          f"{out['tokens']} tokens, {out['decode_steps']} decode steps, "
          f"wall {out['wall_s']:.3f} s, "
          f"{out['tokens'] / out['wall_s']:.1f} tokens/s wall "
          f"(includes sandbox starts)")
    print(f"reference: rel L2 errors {out['rel_errs']} "
          f"(limit {out['tol']:.0e}), argmax agrees "
          f"{out['argmax_agree']}/{out['n_reference']}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
