"""LiveBackend: dual-mode sandbox payloads behind the DES orchestrator.

Two execution modes, selected per function by ``LiveFunctionSpec.mode``:

  * ``process``   — the sandbox is an in-process ``Replica`` +
    ``ContinuousBatcher``. Creation cost is model-state construction only
    (params + KV cache, ~ms) because the XLA executables come from the
    process-global ``ExecutableCache`` — the live analogue of a snapshot
    restore against pre-created state.
  * ``container`` — the sandbox is an isolated subprocess worker
    (repro/live/container.py): spawn + import + replica build, hundreds of
    ms to seconds, the containerd analogue. Its executables cannot be
    shared in-process; the JAX *persistent* compilation cache directory
    plays the shared-cache role across worker processes instead. CPU only:
    on a TPU the parent holds the chip, so container mode refuses to start.

Wiring into the DES (all hooks are no-ops unless a backend is installed —
the default path stays bit-identical):

  * ``create_hook(sandbox)``   — called by ``WorkerDaemon.create_sandbox``
    after the modeled boot; builds the replica, logs cold/warm wall time.
  * ``teardown_hook(sid, drain=True)`` — called by
    ``WorkerDaemon.kill_sandbox`` (drain: in-slot requests finish first,
    matching the DES ``teardown_drain_grace`` semantics) and by
    ``fail_node`` (drain=False: in-slot requests fail).
  * ``admit``/``collect``      — the invoke path. ``WorkerDaemon.execute``
    admits the invocation's ``LiveRequest`` into the target sandbox's
    batcher *before* yielding its dispatch-overhead timeout, so requests
    that are concurrent in sim time land in slots together and share
    decode steps; ``collect`` then pumps the batcher until the request's
    slot finishes, billing only the wall time this request actually spent
    pumping (work done while pumping for a neighbour is the batching win).

Spans (``jax.profiler.TraceAnnotation``, free when no profiler runs):
``live.admit`` and ``live.pump`` on the invoke path, ``live.create.params``,
``live.create.cache`` and ``live.create.warm`` in a process-mode creation.
Each ``LiveRequest`` gets ``perf_counter`` stamps: ``t_admit`` here,
``t_first``/``t_last`` from the batcher (process mode) and ``t_collected``
when ``collect`` returns.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.request import LiveRequest
from repro.models.api import RunConfig


@dataclass(frozen=True)
class LiveFunctionSpec:
    """Per-function live-execution config (the ``live_mode`` knobs)."""

    cfg: ArchConfig
    mode: str = "process"            # "process" | "container"
    run_cfg: Optional[RunConfig] = None
    max_seq: int = 64                # replica KV-cache length
    max_slots: int = 4               # batcher slots == DP concurrency
    default_max_new: int = 8         # when a LiveRequest leaves it unset


@dataclass
class LiveTicket:
    """Handle returned by ``admit``; redeemed by ``collect``."""

    sandbox_id: int
    rid: int                         # batcher request id
    request: LiveRequest
    admit_peers: int = 0             # active slots present at admission


# the batcher counters LiveBackend.batcher_counts sums
BATCHER_COUNTS = ("steps", "slot_steps", "capacity_steps", "prompt_tokens",
                  "generated_tokens")


@contextlib.contextmanager
def _phase(name: str, sandbox_id: int, into: Dict[str, float]):
    """Span ``live.create.<name>``; its wall seconds go to ``<name>_s``."""
    t0 = time.perf_counter()
    with TraceAnnotation(f"live.create.{name}", sandbox=sandbox_id):
        yield
    into[f"{name}_s"] = round(time.perf_counter() - t0, 6)


class _ProcessSandbox:
    """In-process replica + batcher (mode="process")."""

    def __init__(self, sandbox_id: int, spec: LiveFunctionSpec, replica,
                 batcher):
        self.sandbox_id = sandbox_id
        self.spec = spec
        self.replica = replica
        self.batcher = batcher

    def admit(self, req: LiveRequest) -> Tuple[int, int]:
        """Admit into a free slot; returns (rid, co-resident active slots)."""
        peers = sum(1 for s in self.batcher.slots if s.active)
        rid = self.batcher.add_request(
            list(req.prompt), req.max_new_tokens or self.spec.default_max_new)
        return rid, peers

    def pump(self, rid: int) -> Tuple[Optional[List[int]], int]:
        """Step the shared batcher until ``rid`` finishes; returns (tokens,
        max co-resident active slots seen while pumping)."""
        peers = steps = 0
        with TraceAnnotation("live.pump", sandbox=self.sandbox_id,
                             rid=rid) as span:
            while rid not in self.batcher.finished:
                active = sum(1 for s in self.batcher.slots if s.active)
                if active == 0:
                    break                # aborted out from under us
                peers = max(peers, active)
                self.batcher.step()
                steps += 1
            span.set_metadata(steps=steps)
        return self.batcher.finished.get(rid), peers

    def token_times(self, rid: int) -> Optional[Tuple[float, float]]:
        """``perf_counter`` stamps of ``rid``'s first and last output token."""
        return self.batcher.token_times.pop(rid, None)

    def counts(self) -> Dict[str, int]:
        b = self.batcher
        return {"steps": b.steps, "slot_steps": b.slot_steps,
                "capacity_steps": b.steps * b.max_slots,
                "prompt_tokens": b.prompt_tokens,
                "generated_tokens": b.generated_tokens}

    def drain(self) -> Dict[int, List[int]]:
        return self.batcher.run_until_done()

    def abort(self) -> List[int]:
        return self.batcher.abort()

    def close(self) -> None:
        pass


class LiveBackend:
    """Owns every live sandbox runtime; plugs into Cluster via hooks."""

    def __init__(self, specs: Optional[Dict[str, LiveFunctionSpec]] = None,
                 default_spec: Optional[LiveFunctionSpec] = None,
                 exec_cache=None):
        from repro.serving.exec_cache import default_cache

        self.specs = dict(specs or {})
        self.default_spec = default_spec
        self.exec_cache = exec_cache if exec_cache is not None \
            else default_cache()
        self.sandboxes: Dict[int, object] = {}       # sid -> runtime
        # results that outlive their runtime (graceful teardown drains
        # in-slot requests; their tickets must still collect): tokens and
        # their first/last stamps
        self._orphaned: Dict[Tuple[int, int], tuple] = {}
        self._failed_sids: set = set()               # torn down drain=False
        # -- observability (monitoring.render_metrics) ----------------------
        self.start_log: List[dict] = []              # one row per creation
        self.teardowns = 0
        self.invokes = 0
        self.invoke_seconds_total = 0.0
        self.tokens_total = 0
        self.batched_invokes = 0                     # shared >=1 decode step
        # batcher counters of runtimes already torn down
        self._retired_counts = dict.fromkeys(BATCHER_COUNTS, 0)

    # -- config ------------------------------------------------------------
    def spec_for(self, function_name: str) -> LiveFunctionSpec:
        spec = self.specs.get(function_name, self.default_spec)
        if spec is None:
            raise KeyError(f"no LiveFunctionSpec for {function_name!r} "
                           "and no default_spec")
        return spec

    @property
    def replicas_live(self) -> int:
        return len(self.sandboxes)

    def cache_stats(self) -> dict:
        return self.exec_cache.stats()

    def batcher_counts(self) -> Dict[str, int]:
        """The ``BATCHER_COUNTS`` counters, summed over the in-process
        batchers, live and torn down (a container's batcher lives in its
        worker process and is not counted)."""
        out = dict(self._retired_counts)
        for rt in self.sandboxes.values():
            if isinstance(rt, _ProcessSandbox):
                for k, v in rt.counts().items():
                    out[k] += v
        return out

    # -- WorkerDaemon hooks --------------------------------------------------
    def create_hook(self, sandbox) -> None:
        """Build the real payload for a freshly booted sandbox. Wall time
        (and whether the executable cache was cold) lands in start_log —
        the measured per-phase costs the bench turns into a calibrated
        DirigentCosts candidate. A process-mode row also times its phases:
        ``params_s`` (weights made and on the device), ``cache_s`` (the KV
        cache) and ``warm_s`` (the decode executable traced or found); a
        container's phases run in its worker and read ``None``."""
        spec = self.spec_for(sandbox.function_name)
        sid = sandbox.sandbox_id
        t0 = time.perf_counter()
        misses0 = self.exec_cache.misses
        phases = dict.fromkeys(("params_s", "cache_s", "warm_s"))
        if spec.mode == "container":
            from repro.live.container import ContainerSandbox

            rt = ContainerSandbox(spec, seed=sid)
            cold = rt.cold
        else:
            from repro.serving.engine import ContinuousBatcher, Replica

            with _phase("params", sid, phases):
                replica = Replica(spec.cfg, rng_seed=sid,
                                  max_seq=spec.max_seq, run_cfg=spec.run_cfg,
                                  exec_cache=self.exec_cache)
                jax.block_until_ready(replica.params)
            with _phase("cache", sid, phases):
                batcher = ContinuousBatcher(replica, max_slots=spec.max_slots)
                jax.block_until_ready(batcher.cache)
            rt = _ProcessSandbox(sid, spec, replica, batcher)
            # bill the executable trace to creation (not the first invoke):
            # a cold cache compiles here; a warm one returns instantly
            shape = ShapeSpec("live", spec.max_seq, spec.max_slots, "decode")
            with _phase("warm", sid, phases):
                compile_s = self.exec_cache.warm(spec.cfg, shape,
                                                 run_cfg=replica.run_cfg,
                                                 params=replica.params)
            # cold = this creation built the entry OR traced a new shape
            cold = self.exec_cache.misses > misses0 or compile_s > 0.0
        self.sandboxes[sid] = rt
        self._failed_sids.discard(sid)
        self.start_log.append({
            "sandbox_id": sid,
            "function": sandbox.function_name,
            "mode": spec.mode,
            "cold": cold,
            "wall_s": round(time.perf_counter() - t0, 6),
            **phases,
        })

    def teardown_hook(self, sandbox_id: int, drain: bool = True) -> None:
        """Reclaim a sandbox's replica. drain=True finishes in-slot
        requests first (the DES drain-grace analogue); drain=False fails
        them (node death)."""
        rt = self.sandboxes.pop(sandbox_id, None)
        if rt is None:
            return
        self.teardowns += 1
        if drain:
            for rid, toks in rt.drain().items():
                self._orphaned[(sandbox_id, rid)] = (toks,
                                                     rt.token_times(rid))
        else:
            rt.abort()
            self._failed_sids.add(sandbox_id)
        if isinstance(rt, _ProcessSandbox):
            for k, v in rt.counts().items():
                self._retired_counts[k] += v
        rt.close()

    # -- invoke path ---------------------------------------------------------
    def admit(self, sandbox_id: int, req: LiveRequest) -> LiveTicket:
        rt = self.sandboxes.get(sandbox_id)
        if rt is None:
            raise RuntimeError(f"live sandbox {sandbox_id} gone")
        with TraceAnnotation("live.admit", sandbox=sandbox_id) as span:
            rid, peers = rt.admit(req)
            span.set_metadata(rid=rid)
        req.t_admit = time.perf_counter()
        return LiveTicket(sandbox_id=sandbox_id, rid=rid, request=req,
                          admit_peers=peers)

    def collect(self, ticket: LiveTicket) -> LiveRequest:
        """Run the ticket's request to completion; fills the LiveRequest
        in place and returns it. Wall time spent *here* is what the worker
        bills to the sim clock."""
        req = ticket.request
        t0 = time.perf_counter()
        key = (ticket.sandbox_id, ticket.rid)
        rt = self.sandboxes.get(ticket.sandbox_id)
        toks: Optional[List[int]] = None
        times: Optional[Tuple[float, float]] = None
        peers = 0
        if key in self._orphaned:                # finished during teardown
            toks, times = self._orphaned.pop(key)
        elif rt is not None:
            toks, peers = rt.pump(ticket.rid)
            times = rt.token_times(ticket.rid)
        if toks is None:
            req.failed = True
            req.failure_reason = (
                "sandbox failed with request in slot"
                if ticket.sandbox_id in self._failed_sids
                else "request aborted")
        else:
            req.tokens = toks
            if times is not None:
                req.t_first, req.t_last = times
            # shared decode steps with: slots present when we were admitted
            # (we free-rode on their pump) or co-active while we pumped
            req.batched_with = max(ticket.admit_peers, peers - 1, 0)
            self.tokens_total += len(toks)
            if req.batched_with:
                self.batched_invokes += 1
        req.t_collected = time.perf_counter()
        req.wall_s = req.t_collected - t0
        self.invokes += 1
        self.invoke_seconds_total += req.wall_s
        return req

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Tear down every remaining runtime (bench/test cleanup)."""
        for sid in list(self.sandboxes):
            self.teardown_hook(sid, drain=False)
