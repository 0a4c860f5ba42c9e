"""Serving engine: the compute payload a Dirigent "sandbox" hosts.

Two layers:

  * ``Replica`` — one model instance: jitted prefill/decode, greedy or
    temperature sampling, simple per-request ``generate``. This is what the
    live-mode worker hook instantiates per sandbox (examples/serve_llm.py).
  * ``ContinuousBatcher`` — slot-based continuous batching on top of a
    Replica: a fixed (max_slots, max_seq) KV cache; new requests are admitted
    into free slots mid-flight and their prompts are consumed token-by-token
    while other slots generate (decode-only lockstep, per-slot cache
    lengths). This is the data-plane concurrency-throttling analogue: the
    sandbox advertises ``max_slots`` as its concurrency capacity to the
    Dirigent DP.

Each batcher step is a ``jax.profiler.TraceAnnotation`` span, ``engine.step``,
with children ``engine.prepare``, ``engine.dispatch``, ``engine.fetch`` and
``engine.update``: free when no profiler runs, and on the device trace's
clock when one does. Its stats (``slots``, ``active``, ``prompt``,
``generated``) are also kept as cumulative counters on the batcher.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models.api import RunConfig
from repro.serving.exec_cache import ExecutableCache, default_cache


def sample_token(logits: jax.Array, rng: Optional[jax.Array] = None,
                 temperature: float = 0.0, top_k: int = 0) -> jax.Array:
    """logits: (B, V) -> (B,) int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    # categorical draws full-shape Gumbel noise: rows sample independently
    return jax.random.categorical(rng, logits).astype(jnp.int32)


# execution knobs every replica uses unless its function spec says otherwise
SERVE_RUN_CFG = RunConfig(q_chunk=64, kv_chunk=64, seq_chunk=16)


class Replica:
    def __init__(self, cfg: ArchConfig, params=None, rng_seed: int = 0,
                 max_seq: int = 256, run_cfg: Optional[RunConfig] = None,
                 exec_cache: Optional[ExecutableCache] = None):
        self.cfg = cfg
        self.run_cfg = run_cfg or SERVE_RUN_CFG
        # prefill and decode executables come from the shared process-global
        # cache: the second replica of a (cfg, run_cfg) pays model-state
        # construction but zero XLA recompilation (serving/exec_cache.py)
        self.exec_cache = exec_cache if exec_cache is not None \
            else default_cache()
        entry = self.exec_cache.get(cfg, self.run_cfg)
        self.model = entry.model
        self.max_seq = max_seq
        if params is None:
            params = self.model.init_params(jax.random.PRNGKey(rng_seed))
        self.params = params
        self._decode = entry.decode
        self._prefill = entry.prefill

    def new_cache(self, batch: int):
        shape = ShapeSpec("serve", self.max_seq, batch, "decode")
        return self.model.init_cache(shape, batch=batch)

    def generate(self, prompt_tokens: List[int], max_new_tokens: int = 16,
                 temperature: float = 0.0, seed: int = 0) -> List[int]:
        """Single-request generation (prompt consumed via decode steps)."""
        cache = self.new_cache(1)
        toks = list(prompt_tokens)
        out: List[int] = []
        rng = jax.random.PRNGKey(seed)
        logits = None
        for t, tok in enumerate(toks):
            batch = {"tokens": jnp.array([[tok]], jnp.int32),
                     "cache_len": jnp.array(t, jnp.int32)}
            logits, cache = self._decode(self.params, cache, batch)
        pos = len(toks)
        for i in range(max_new_tokens):
            rng, sub = jax.random.split(rng)
            nxt = int(sample_token(logits, sub, temperature)[0])
            out.append(nxt)
            batch = {"tokens": jnp.array([[nxt]], jnp.int32),
                     "cache_len": jnp.array(pos, jnp.int32)}
            logits, cache = self._decode(self.params, cache, batch)
            pos += 1
        return out


@dataclass
class Slot:
    active: bool = False
    request_id: int = -1
    pending: List[int] = field(default_factory=list)   # prompt not yet fed
    generated: List[int] = field(default_factory=list)
    length: int = 0
    max_new: int = 0
    t_first: float = 0.0             # perf_counter of the first output token


class ContinuousBatcher:
    """Decode-only continuous batching with per-slot cache lengths.

    Counters, cumulative over steps: ``steps``; ``slot_steps``, active slots
    summed over steps (the capacity is ``steps * max_slots``);
    ``prompt_tokens``, slots that fed a prompt token and generated nothing
    (every prompt token but the last: the step that feeds the last one
    generates the first output token); ``generated_tokens``. So
    ``prompt_tokens + generated_tokens == slot_steps``. ``token_times`` maps
    a finished request id to the ``perf_counter`` stamps of its first and
    last output token."""

    def __init__(self, replica: Replica, max_slots: int = 8):
        self.replica = replica
        self.max_slots = max_slots
        self.slots = [Slot() for _ in range(max_slots)]
        self.cache = replica.new_cache(max_slots)
        self._next_id = 0
        self.finished: Dict[int, List[int]] = {}
        self.token_times: Dict[int, Tuple[float, float]] = {}
        self.steps = 0
        self.slot_steps = 0
        self.prompt_tokens = 0
        self.generated_tokens = 0

    @property
    def free_slots(self) -> int:
        return sum(1 for s in self.slots if not s.active)

    def add_request(self, prompt: List[int], max_new: int = 16) -> int:
        for slot in self.slots:
            if not slot.active:
                rid = self._next_id
                self._next_id += 1
                slot.active = True
                slot.request_id = rid
                slot.pending = list(prompt)
                slot.generated = []
                slot.length = 0
                slot.max_new = max_new
                return rid
        raise RuntimeError("no free slot (throttle at the data plane)")

    def step(self) -> List[int]:
        """One lockstep decode over all slots; returns finished request ids."""
        if all(not s.active for s in self.slots):
            return []
        with TraceAnnotation("engine.step") as span:
            with TraceAnnotation("engine.prepare"):
                tokens = np.zeros((self.max_slots, 1), np.int32)
                lens = np.zeros((self.max_slots,), np.int32)
                active = prompt = 0
                for i, s in enumerate(self.slots):
                    lens[i] = s.length
                    if not s.active:
                        continue
                    active += 1
                    if s.pending:
                        tokens[i, 0] = s.pending.pop(0)
                        if s.pending:
                            prompt += 1        # no output from this step
                    else:
                        tokens[i, 0] = (s.generated[-1] if s.generated
                                        else 0)
                batch = {"tokens": jnp.asarray(tokens),
                         "cache_len": jnp.asarray(lens)}
            with TraceAnnotation("engine.dispatch"):
                logits, self.cache = self.replica._decode(
                    self.replica.params, self.cache, batch)
            self.steps += 1
            with TraceAnnotation("engine.fetch"):
                argmax = np.asarray(jnp.argmax(logits, axis=-1))
            now = time.perf_counter()
            done: List[int] = []
            with TraceAnnotation("engine.update"):
                for i, s in enumerate(self.slots):
                    if not s.active:
                        continue
                    s.length += 1
                    if s.pending:
                        continue               # still consuming the prompt
                    if not s.generated:
                        s.t_first = now
                    s.generated.append(int(argmax[i]))
                    if (len(s.generated) >= s.max_new
                            or s.length >= self.replica.max_seq - 1):
                        self.finished[s.request_id] = s.generated
                        self.token_times[s.request_id] = (s.t_first, now)
                        done.append(s.request_id)
                        s.active = False
            span.set_metadata(slots=self.max_slots, active=active,
                              prompt=prompt, generated=active - prompt)
        self.slot_steps += active
        self.prompt_tokens += prompt
        self.generated_tokens += active - prompt
        return done

    def abort(self) -> List[int]:
        """Kill every in-slot request without finishing it (node-failure
        semantics; graceful teardown drains via ``run_until_done`` instead,
        mirroring the DES ``teardown_drain_grace``). Aborted requests never
        appear in ``finished``; returns their request ids."""
        killed: List[int] = []
        for s in self.slots:
            if s.active:
                killed.append(s.request_id)
                s.active = False
                s.pending = []
                s.generated = []
        return killed

    def run_until_done(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            if all(not s.active for s in self.slots):
                break
            self.step()
        return self.finished
