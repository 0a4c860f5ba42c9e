"""Container-mode live sandbox: an isolated subprocess replica worker.

The containerd analogue of the live backend's process mode: the sandbox is
a real OS process with its own JAX runtime, started with the ``spawn``
method (fork deadlocks under JAX's thread pools) and driven over a pipe
with a tiny admit/collect protocol mirroring ``_ProcessSandbox``.

An in-process ``ExecutableCache`` cannot help across process boundaries,
so the shared-executable story here is the JAX *persistent compilation
cache* (``repro.compile_cache``), which every child opens at the same
directory. The first worker of a config pays the XLA compile and populates
it; later workers (the "warm container" path) deserialize the executable
instead of recompiling — the same cold/warm split the in-process cache
gives, at container granularity. A child reports how many executables it
loaded from that cache, and a creation is cold when it loaded none.

One process per chip: a parent that has touched JAX holds the TPU, and a
spawned child then either fails on the TPU library's lock or quietly starts
a CPU backend. So container mode refuses to start under a TPU backend, and
every child checks that it runs on the parent's platform.

Protocol (parent -> child):
    (startup)                    -> ("ready", cache_hits) | ("error", why)
    ("admit", prompt, max_new)   -> ("rid", rid, peers)
    ("collect", rid)             -> ("done", tokens_or_None, peers)
    ("shutdown", drain)          -> ("bye", finished_dict)
"""
from __future__ import annotations

import multiprocessing as mp
import time
from typing import Dict, List, Optional, Tuple

# ready-ack budget: tiny-config CPU compile is ~2-4 s; a hung child should
# fail the creation, not the whole bench
_READY_TIMEOUT_S = 120.0


def _child_main(conn, spec, seed: int, platform: str,
                persistent_cache: bool) -> None:
    """Subprocess entry point (module-level: spawn must import it)."""
    import jax

    if jax.default_backend() != platform:
        conn.send(("error", f"container worker started on "
                            f"{jax.default_backend()}, parent runs on "
                            f"{platform}"))
        conn.close()
        return
    if persistent_cache:
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        # keep the small compiles too (JAX skips those under a second)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    hits = []

    def count_hit(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(count_hit)
    from repro.serving.engine import ContinuousBatcher, Replica
    from repro.serving.exec_cache import ExecutableCache

    # fresh per-process cache: isolation is the point of container mode
    replica = Replica(spec.cfg, rng_seed=seed, max_seq=spec.max_seq,
                      run_cfg=spec.run_cfg,
                      exec_cache=ExecutableCache())
    batcher = ContinuousBatcher(replica, max_slots=spec.max_slots)
    # warm the batcher's decode signature before acking ready, so creation
    # wall time includes the compile (cold) or persistent-cache load (warm)
    warm_rid = batcher.add_request([1], 1)
    batcher.run_until_done()
    batcher.finished.pop(warm_rid, None)
    conn.send(("ready", len(hits)))
    while True:
        msg = conn.recv()
        if msg[0] == "admit":
            _, prompt, max_new = msg
            peers = sum(1 for s in batcher.slots if s.active)
            rid = batcher.add_request(list(prompt), max_new)
            conn.send(("rid", rid, peers))
        elif msg[0] == "collect":
            rid = msg[1]
            peers = 0
            while rid not in batcher.finished:
                active = sum(1 for s in batcher.slots if s.active)
                if active == 0:
                    break
                peers = max(peers, active)
                batcher.step()
            conn.send(("done", batcher.finished.get(rid), peers))
        elif msg[0] == "shutdown":
            if msg[1]:
                batcher.run_until_done()
            else:
                batcher.abort()
            conn.send(("bye", dict(batcher.finished)))
            break
    conn.close()


class ContainerSandbox:
    """Parent-side handle; API mirrors ``_ProcessSandbox``."""

    def __init__(self, spec, seed: int = 0, persistent_cache: bool = True):
        import jax

        platform = jax.default_backend()
        if platform == "tpu":
            raise RuntimeError(
                "container mode cannot run on a TPU: this process holds the "
                "chip, and a spawned worker can neither share it nor take "
                "it; serve live functions in process mode on a chip")
        self.spec = spec
        ctx = mp.get_context("spawn")
        self._conn, child_conn = ctx.Pipe()
        self.proc = ctx.Process(
            target=_child_main,
            args=(child_conn, spec, seed, platform, persistent_cache),
            daemon=True)
        t0 = time.perf_counter()
        self.proc.start()
        child_conn.close()
        msg = ("error", "container worker never became ready")
        if self._conn.poll(_READY_TIMEOUT_S):
            try:
                msg = self._conn.recv()
            except EOFError:
                msg = ("error", "container worker exited during startup")
        if msg[0] != "ready":
            self.proc.kill()
            raise RuntimeError(msg[1])
        # cold = the worker compiled its executables instead of loading them
        self.cold = msg[1] == 0
        self.start_wall_s = time.perf_counter() - t0
        self._finished: Dict[int, List[int]] = {}

    def admit(self, req) -> Tuple[int, int]:
        self._conn.send(("admit", list(req.prompt),
                         req.max_new_tokens or self.spec.default_max_new))
        _, rid, peers = self._conn.recv()
        return rid, peers

    def pump(self, rid: int) -> Tuple[Optional[List[int]], int]:
        if rid in self._finished:
            return self._finished.pop(rid), 0
        self._conn.send(("collect", rid))
        _, toks, peers = self._conn.recv()
        return toks, peers

    def token_times(self, rid: int) -> None:
        """Output-token stamps stay in the worker process: none here."""
        return None

    def drain(self) -> Dict[int, List[int]]:
        return self._shutdown(drain=True)

    def abort(self) -> List[int]:
        self._shutdown(drain=False)
        return []

    def _shutdown(self, drain: bool) -> Dict[int, List[int]]:
        finished: Dict[int, List[int]] = {}
        try:
            self._conn.send(("shutdown", drain))
            if self._conn.poll(_READY_TIMEOUT_S):
                msg = self._conn.recv()
                if msg[0] == "bye":
                    finished = msg[1]
        except (BrokenPipeError, EOFError, OSError):
            pass
        self.proc.join(timeout=10.0)
        if self.proc.is_alive():
            self.proc.kill()
        return finished

    def close(self) -> None:
        if self.proc.is_alive():
            self._shutdown(drain=False)
        self._conn.close()
