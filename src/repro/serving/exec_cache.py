"""Process-global compiled-executable cache for live-mode replicas.

The live analogue of the paper's sandbox-churn insight: Dirigent makes
sandbox *creation* cheap by keeping the expensive state (VM snapshots,
pooled network configs) out of the per-creation critical path. For a JAX
replica the expensive state is the XLA executable — compiling the decode
step of even a truncated smollm config costs ~1-2 s on CPU while building
the model state (params + KV cache) costs ~10 ms. Without sharing, every
sandbox cold start pays the compile; with this cache a cold start pays
model-state construction only, which is what makes live creation throughput
track the orchestrator rather than the compiler (ISSUE 10 acceptance:
warm >= 10x cold).

Keying is ``(ArchConfig, RunConfig, mode)`` for the jitted callables —
both are frozen dataclasses, so they hash structurally — plus a per-entry
``shapes`` table recording which ``ShapeSpec`` signatures have been traced
(jit compiles one executable per input signature; ``warm()`` forces the
trace for a shape up front and records its compile wall time). ``mode``
keeps process-mode entries distinct from container-mode bookkeeping
entries: a subprocess worker cannot share an in-process executable, so its
"shared cache" is the on-disk persistent compilation cache
(``repro.live.container``), and its entries here only carry the per-shape
compile-time observations used for cost calibration.

The model objects handed out are safe to share between replicas: a
``Model`` holds only ``(cfg, run_cfg)`` — params and caches are passed
explicitly through every jitted call — so N replicas of one config share
one traced executable and differ only in their param/cache pytrees.
"""
from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models.api import RunConfig, build_model


@dataclass
class CacheEntry:
    """One (arch, run_cfg, mode) entry: shared model + jitted callables."""

    cfg: ArchConfig
    run_cfg: RunConfig
    mode: str
    model: object = None
    decode: object = None          # jit(model.decode_step), cache donated
    prefill: object = None         # jit(model.forward)
    # ShapeSpec -> compile wall seconds observed when the shape was warmed
    shapes: Dict[ShapeSpec, float] = field(default_factory=dict)

    def compiled_executables(self) -> int:
        """Distinct traced signatures across decode + prefill (jax's own
        per-jit trace count; the regression-test observable)."""
        n = 0
        for fn in (self.decode, self.prefill):
            if fn is not None and hasattr(fn, "_cache_size"):
                n += fn._cache_size()
        return n


class ExecutableCache:
    """LRU cache of jitted replica executables, shared process-wide.

    ``capacity`` bounds the number of distinct (cfg, run_cfg, mode) entries
    (None = unbounded; eviction drops the jitted wrappers, letting XLA free
    the executables). Hit/miss counters feed the
    ``dirigent_live_exec_cache_*`` metrics.
    """

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity
        self._entries: "OrderedDict[Tuple, CacheEntry]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, cfg: ArchConfig, run_cfg: Optional[RunConfig] = None,
            mode: str = "process") -> CacheEntry:
        """Return the shared entry for (cfg, run_cfg, mode), building the
        model + jitted wrappers on first use (the cold path a warm sandbox
        creation skips)."""
        import jax

        run_cfg = run_cfg or RunConfig()
        key = (cfg, run_cfg, mode)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.hits += 1
                self._entries.move_to_end(key)
                return entry
            self.misses += 1
            entry = CacheEntry(cfg=cfg, run_cfg=run_cfg, mode=mode)
            entry.model = build_model(cfg, run_cfg)
            # the cache is donated: the step writes its new rows in place
            entry.decode = jax.jit(entry.model.decode_step, donate_argnums=1)
            entry.prefill = jax.jit(entry.model.forward)
            self._entries[key] = entry
            if self.capacity is not None:
                while len(self._entries) > self.capacity:
                    self._entries.popitem(last=False)
                    self.evictions += 1
            return entry

    def warm(self, cfg: ArchConfig, shape: ShapeSpec,
             run_cfg: Optional[RunConfig] = None,
             mode: str = "process", params=None) -> float:
        """Force-trace the decode executable for ``shape`` (batch =
        ``shape.global_batch``, cache length ``shape.seq_len``) and record
        its compile wall time under the entry. Returns the seconds spent
        (~0 when the signature was already traced). This is what a
        container-mode worker's boot does against the *persistent* cache;
        process mode gets it implicitly on the first decode step."""
        import jax
        import jax.numpy as jnp

        entry = self.get(cfg, run_cfg, mode)
        if shape in entry.shapes:
            return 0.0
        if params is None:
            params = entry.model.init_params(jax.random.PRNGKey(0))
        cache = entry.model.init_cache(shape, batch=shape.global_batch)
        batch = {"tokens": jnp.zeros((shape.global_batch, 1), jnp.int32),
                 "cache_len": jnp.zeros((shape.global_batch,), jnp.int32)}
        t0 = time.perf_counter()
        logits, _ = entry.decode(params, cache, batch)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        entry.shapes[shape] = dt
        return dt

    def compiled_executables(self) -> int:
        return sum(e.compiled_executables() for e in self._entries.values())

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "compiled_executables": self.compiled_executables()}


# -- the process-global default ------------------------------------------------
_DEFAULT: Optional[ExecutableCache] = None
_DEFAULT_LOCK = threading.Lock()


def default_cache() -> ExecutableCache:
    """The process-global cache every Replica shares unless told otherwise."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = ExecutableCache()
        return _DEFAULT


def reset_default_cache() -> ExecutableCache:
    """Swap in a fresh global cache (tests measuring cold compiles)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = ExecutableCache()
        return _DEFAULT
