"""The timed path broken underneath, and ``correct`` comes out false: once
for each fault a serving cell can have. (A single-chip cell has no exchange
between chips to leave out.)"""
import pytest


def _alter_tokens(monkeypatch):
    """Every generated token changed where the batcher produces it."""
    from repro.serving.engine import ContinuousBatcher

    real = ContinuousBatcher.step

    def step(self):
        before = {id(s): len(s.generated) for s in self.slots}
        out = real(self)
        vocab = self.replica.cfg.vocab
        for s in self.slots:
            if len(s.generated) > before[id(s)]:
                s.generated[-1] = (s.generated[-1] + 1) % vocab
        return out
    monkeypatch.setattr(ContinuousBatcher, "step", step)


def _stale_cache(monkeypatch):
    """The decode step hands back its cache unchanged: a copy of the input
    cache taken before the call, since a call that donates its input cache
    deletes that buffer."""
    import jax
    import jax.numpy as jnp

    from repro.serving.exec_cache import ExecutableCache

    real = ExecutableCache.get

    def get(self, *a, **kw):
        entry = real(self, *a, **kw)
        if not getattr(entry, "_broken", False):
            decode = entry.decode

            def stale(p, c, b):
                before = jax.tree.map(jnp.copy, c)
                return decode(p, c, b)[0], before
            # through monkeypatch, so the shared cache is mended afterwards
            monkeypatch.setattr(entry, "decode", stale)
            monkeypatch.setattr(entry, "_broken", True, raising=False)
        return entry
    monkeypatch.setattr(ExecutableCache, "get", get)


def _donate_cache(monkeypatch):
    """The decode step donates its input cache, as the program may: the
    call deletes the buffers it was given. (A new cache's keys and values
    are one buffer in the program, which cannot be donated twice: each
    gets its own here.)"""
    import jax
    import jax.numpy as jnp

    from repro.serving.exec_cache import ExecutableCache

    real = ExecutableCache.get

    def get(self, *a, **kw):
        entry = real(self, *a, **kw)
        if not getattr(entry, "_donating", False):
            model, init_cache = entry.model, entry.model.init_cache
            monkeypatch.setattr(model, "init_cache", lambda *a, **kw: (
                jax.tree.map(jnp.copy, init_cache(*a, **kw))))
            monkeypatch.setattr(entry, "decode", jax.jit(
                model.decode_step, donate_argnums=1))
            monkeypatch.setattr(entry, "_donating", True, raising=False)
        return entry
    monkeypatch.setattr(ExecutableCache, "get", get)


def _drop_half(monkeypatch):
    """Every other admitted request is left out of the batch."""
    from repro.live.backend import _ProcessSandbox

    real = _ProcessSandbox.admit
    n = {"i": 0}

    def admit(self, req):
        n["i"] += 1
        if n["i"] % 2:
            return -1 - n["i"], 0          # a ticket no slot will finish
        return real(self, req)
    monkeypatch.setattr(_ProcessSandbox, "admit", admit)


# (fault, whether it goes in after set-up): a fault in what set-up builds
# goes in first; one that would fail the warm-up goes in after it
FAULTS = [(_alter_tokens, False), (_stale_cache, False), (_drop_half, True)]


@pytest.mark.parametrize("fault,after_setup", FAULTS,
                         ids=[f.__name__.strip("_") for f, _ in FAULTS])
@pytest.mark.parametrize("workload", ["llm.saturate", "llm.steady",
                                      "faas.burst"])
def test_fault_makes_run_incorrect(tree, rehearse, monkeypatch, fault,
                                   after_setup, workload):
    from bench import harness
    from bench.tests.tiny import LATER, add_cell

    if workload in LATER:
        add_cell(tree, workload)

    if after_setup:
        real = harness.Harness.prewarm

        def prewarm(self):
            real(self)
            fault(monkeypatch)
        monkeypatch.setattr(harness.Harness, "prewarm", prewarm)
    else:
        fault(monkeypatch)
    out = rehearse(workload)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("fault", [None, _stale_cache],
                         ids=["sound", "stale_cache"])
def test_stale_cache_fault_holds_with_a_donated_cache(tree, rehearse,
                                                      monkeypatch, fault):
    """With the cache donated the program still serves correctly, and the
    stale-cache fault, which hands back a copy of the input cache, still
    makes the run not correct (handing back the input itself would hand
    back a deleted buffer)."""
    _donate_cache(monkeypatch)
    if fault is not None:
        fault(monkeypatch)
    out = rehearse("llm.saturate")
    assert out["correct"] is (fault is None), out["checks"]
