"""Live execution mode tests: shared executable cache, batcher-under-churn
semantics, LiveBackend hooks, and the DES invoke path with real payloads."""
import pytest

from repro.configs import get_config
from repro.core import Cluster
from repro.core.abstractions import Sandbox
from repro.core.monitoring import render_metrics
from repro.core.request import LiveRequest
from repro.live import LiveBackend, LiveFunctionSpec
from repro.serving.engine import ContinuousBatcher, Replica
from repro.serving.exec_cache import ExecutableCache
from repro.simcore import Environment

TINY = get_config("smollm-360m").reduced(
    n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=128)


def _sandbox(sid: int, fn: str = "lf0") -> Sandbox:
    return Sandbox(sandbox_id=sid, function_name=fn,
                   ip=(10, 0, 0, 1), port=80, worker_id=0)


def _backend(max_slots: int = 4) -> LiveBackend:
    spec = LiveFunctionSpec(cfg=TINY, mode="process", max_seq=64,
                            max_slots=max_slots, default_max_new=4)
    return LiveBackend(default_spec=spec, exec_cache=ExecutableCache())


# -- shared executable cache (satellite: cold-start double-compile) -----------

def test_second_replica_compiles_zero_new_executables():
    cache = ExecutableCache()
    r1 = Replica(TINY, max_seq=64, exec_cache=cache)
    out1 = r1.generate([1, 2, 3], max_new_tokens=4)
    compiled_after_first = cache.compiled_executables()
    assert compiled_after_first >= 1          # first replica traced decode
    assert cache.misses == 1
    r2 = Replica(TINY, max_seq=64, exec_cache=cache)
    out2 = r2.generate([1, 2, 3], max_new_tokens=4)
    # the regression this cache exists to prevent: a second replica of the
    # same (cfg, run_cfg) must reuse the traced executables, not recompile
    assert cache.compiled_executables() == compiled_after_first
    assert cache.hits >= 1
    assert r2._decode is r1._decode and r2._prefill is r1._prefill
    assert out1 == out2                       # same params seed, same model


def test_replicas_share_executables_not_state():
    cache = ExecutableCache()
    r1 = Replica(TINY, max_seq=64, rng_seed=0, exec_cache=cache)
    r2 = Replica(TINY, max_seq=64, rng_seed=1, exec_cache=cache)
    assert r1.model is r2.model               # stateless: only (cfg, run)
    assert r1.params is not r2.params         # per-replica state


def test_cache_capacity_evicts_lru():
    cache = ExecutableCache(capacity=1)
    cache.get(TINY)
    cache.get(TINY.reduced(n_layers=1, d_model=32, n_heads=2,
                           d_ff=64, vocab=64))
    assert len(cache) == 1 and cache.evictions == 1


def test_warm_traces_shape_once():
    from repro.configs.base import ShapeSpec
    cache = ExecutableCache()
    shape = ShapeSpec("live", 64, 2, "decode")
    dt1 = cache.warm(TINY, shape)
    dt2 = cache.warm(TINY, shape)
    assert dt1 > 0.0 and dt2 == 0.0


# -- ContinuousBatcher under churn (satellite 3) ------------------------------

@pytest.fixture(scope="module")
def shared_replica():
    return Replica(TINY, max_seq=64, exec_cache=ExecutableCache())


def test_slot_admission_mid_flight_under_churn(shared_replica):
    """Admit into slots freed by finished requests while others are still
    decoding; every generation must match its solo run."""
    cb = ContinuousBatcher(shared_replica, max_slots=2)
    outs = {}
    prompts = {0: [1, 2, 3], 1: [4, 5], 2: [6, 7, 8], 3: [9]}
    rids = {cb.add_request(prompts[0], max_new=6): 0,
            cb.add_request(prompts[1], max_new=3): 1}
    pending = [2, 3]
    for _ in range(200):
        done = cb.step()
        for rid in done:
            outs[rids[rid]] = cb.finished[rid]
        # churn: refill freed slots mid-flight
        while pending and cb.free_slots:
            k = pending.pop(0)
            rids[cb.add_request(prompts[k],
                                max_new=6 if k == 2 else 2)] = k
        if len(outs) == 4:
            break
    assert len(outs) == 4
    solo = {k: shared_replica.generate(
        p, max_new_tokens={0: 6, 1: 3, 2: 6, 3: 2}[k])
        for k, p in prompts.items()}
    assert outs == solo


def test_per_slot_cache_length_isolation(shared_replica):
    """Slots advance their cache lengths independently: a long-prompt slot
    must not bleed position state into a short-prompt neighbour."""
    cb = ContinuousBatcher(shared_replica, max_slots=3)
    long_rid = cb.add_request([1, 2, 3, 4, 5, 6, 7, 8], max_new=2)
    for _ in range(3):
        cb.step()
    short_rid = cb.add_request([9], max_new=2)
    lens = {s.request_id: s.length for s in cb.slots if s.active}
    assert lens[long_rid] > lens[short_rid] == 0
    cb.run_until_done()
    assert cb.finished[long_rid] == shared_replica.generate(
        [1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=2)
    assert cb.finished[short_rid] == shared_replica.generate(
        [9], max_new_tokens=2)


def test_teardown_drain_finishes_in_slot_requests():
    """Graceful teardown (kill_sandbox path) drains: requests that were in
    slots still yield their tokens — the wall-side mirror of the DES
    teardown_drain_grace."""
    lb = _backend()
    lb.create_hook(_sandbox(1))
    t1 = lb.admit(1, LiveRequest(prompt=[1, 2], max_new_tokens=3))
    t2 = lb.admit(1, LiveRequest(prompt=[3], max_new_tokens=2))
    lb.teardown_hook(1, True)
    assert lb.replicas_live == 0
    for t in (t1, t2):
        req = lb.collect(t)
        assert not req.failed and len(req.tokens) > 0


def test_teardown_fail_fails_in_slot_requests():
    """Node-death teardown (fail_node path) aborts: in-slot requests fail
    with a reason instead of silently hanging."""
    lb = _backend()
    lb.create_hook(_sandbox(1))
    t1 = lb.admit(1, LiveRequest(prompt=[1, 2], max_new_tokens=3))
    lb.teardown_hook(1, False)
    req = lb.collect(t1)
    assert req.failed and "fail" in req.failure_reason
    assert req.tokens is None


def test_batcher_abort_discards_partials(shared_replica):
    cb = ContinuousBatcher(shared_replica, max_slots=2)
    rid = cb.add_request([1, 2, 3], max_new=8)
    for _ in range(5):
        cb.step()
    killed = cb.abort()
    assert killed == [rid]
    assert rid not in cb.finished
    assert all(not s.active for s in cb.slots)


# -- worker hooks (satellite 1: symmetric reclaim) ----------------------------

def test_worker_kill_sandbox_calls_teardown_hook():
    from repro.core.costmodel import DEFAULT_COSTS
    from repro.core.abstractions import WorkerNodeInfo
    from repro.core.worker import WorkerDaemon

    env = Environment(seed=1)
    calls = []
    w = WorkerDaemon(env, WorkerNodeInfo(0, "w0", (10, 0, 0, 1), 9000),
                     DEFAULT_COSTS.dirigent,
                     teardown_hook=lambda sid, drain: calls.append(
                         (sid, drain)))
    sb = _sandbox(7)
    env.process(w.create_sandbox(sb), name="create")
    env.run(until=5.0)
    assert sb.sandbox_id in w.sandboxes
    env.process(w.kill_sandbox(7), name="kill")
    env.run(until=10.0)
    assert calls == [(7, True)]               # graceful: drain semantics


def test_worker_fail_node_calls_teardown_hook_no_drain():
    from repro.core.costmodel import DEFAULT_COSTS
    from repro.core.abstractions import WorkerNodeInfo
    from repro.core.worker import WorkerDaemon

    env = Environment(seed=1)
    calls = []
    w = WorkerDaemon(env, WorkerNodeInfo(0, "w0", (10, 0, 0, 1), 9000),
                     DEFAULT_COSTS.dirigent,
                     teardown_hook=lambda sid, drain: calls.append(
                         (sid, drain)))
    for sid in (1, 2):
        env.process(w.create_sandbox(_sandbox(sid)), name=f"c{sid}")
    env.run(until=5.0)
    w.fail_node()
    assert sorted(calls) == [(1, False), (2, False)]
    assert not w.sandboxes


# -- end-to-end live invoke path ----------------------------------------------

def _live_cluster(env, lb, n_workers=4):
    cl = Cluster(env, n_workers=n_workers, runtime="firecracker",
                 live_backend=lb, sandbox_concurrency=4)
    cl.start()
    leader = cl.control_plane_leader()
    from repro.core import Function, ScalingConfig
    fn = Function(name="lf0", image_url="img://t", port=80,
                  scaling=ScalingConfig(stable_window=1.0, panic_window=1.0,
                                        scale_to_zero_grace=0.2))
    leader.install_function(fn)
    for dp in cl.data_planes:
        dp.sync_functions(["lf0"])
    return cl


def test_live_invoke_end_to_end_with_batching():
    env = Environment(seed=3)
    lb = _backend()
    cl = _live_cluster(env, lb)
    invs = []

    def driver(env):
        for i in range(5):
            invs.append(cl.invoke("lf0", 0.01, request=LiveRequest(
                prompt=[1, 2, 3], max_new_tokens=4)))
            yield env.timeout(0.001)

    env.process(driver(env), name="driver")
    env.run(until=30.0)
    done = [i for i in invs if i.t_done > 0 and not i.failed]
    assert len(done) == 5
    # every completed invocation executed a real payload
    assert all(i.request.tokens is not None and len(i.request.tokens) == 4
               for i in done)
    # identical requests to one replica produce identical tokens
    assert len({tuple(i.request.tokens) for i in done}) == 1
    # sim-concurrent requests shared decode steps in the batcher
    assert lb.batched_invokes > 0
    # wall time was billed to the sim clock: exec span covers payload wall
    assert all(i.t_done > i.t_exec_start for i in done)
    # creations were warm after the first (shared executable cache)
    colds = [r["cold"] for r in lb.start_log]
    assert colds.count(True) == 1


def test_live_metrics_rendered():
    env = Environment(seed=4)
    lb = _backend()
    cl = _live_cluster(env, lb)

    def driver(env):
        cl.invoke("lf0", 0.01,
                  request=LiveRequest(prompt=[5], max_new_tokens=2))
        yield env.timeout(0.0)

    env.process(driver(env), name="driver")
    env.run(until=10.0)
    m = render_metrics(cl)
    assert "dirigent_live_replicas" in m
    assert "dirigent_live_exec_cache_hits" in m
    assert "dirigent_live_exec_cache_misses" in m
    assert "dirigent_live_invoke_seconds" in m
    assert "dirigent_live_tokens_total" in m


def test_des_only_cluster_renders_no_live_metrics():
    env = Environment(seed=5)
    cl = Cluster(env, n_workers=2)
    cl.start()
    env.run(until=1.0)
    assert "dirigent_live_" not in render_metrics(cl)


def test_scale_to_zero_reclaims_live_replicas():
    env = Environment(seed=6)
    lb = _backend()
    cl = _live_cluster(env, lb)

    def driver(env):
        cl.invoke("lf0", 0.01,
                  request=LiveRequest(prompt=[1], max_new_tokens=2))
        yield env.timeout(0.0)

    env.process(driver(env), name="driver")
    env.run(until=60.0)                       # past scale-to-zero grace
    assert lb.replicas_live == 0              # teardown_hook reclaimed
    assert lb.teardowns >= 1


# -- container mode (subprocess worker; slower, one spawn) --------------------

def _container_spec() -> LiveFunctionSpec:
    return LiveFunctionSpec(cfg=TINY, mode="container", max_seq=64,
                            max_slots=2, default_max_new=3)


def test_container_sandbox_roundtrip(tmp_path, monkeypatch):
    # spawned workers inherit the environment: keep their persistent
    # compile cache in the test's own directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    lb = LiveBackend(default_spec=_container_spec())
    lb.create_hook(_sandbox(1))
    try:
        assert lb.start_log[0]["mode"] == "container"
        t = lb.admit(1, LiveRequest(prompt=[1, 2], max_new_tokens=3))
        req = lb.collect(t)
        assert not req.failed and len(req.tokens) == 3
    finally:
        lb.close()
    assert lb.replicas_live == 0


def test_container_cold_warm_follows_persistent_cache(tmp_path, monkeypatch):
    """A worker is cold when it compiled, warm when it loaded its executables
    from the persistent cache an earlier worker filled; a worker with the
    cache off always compiles."""
    from repro.live.container import ContainerSandbox

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    colds = []
    for persistent in (True, True, False):
        sb = ContainerSandbox(_container_spec(), seed=1,
                              persistent_cache=persistent)
        colds.append(sb.cold)
        sb.close()
    assert colds == [True, False, True]


def test_container_sandbox_refuses_tpu(monkeypatch):
    """One process per chip: a parent on a TPU backend holds the chip, so a
    spawned worker could not run there. Refuse before spawning."""
    import jax

    from repro.live.container import ContainerSandbox

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU"):
        ContainerSandbox(_container_spec())


def test_container_worker_rejects_other_platform():
    """A worker that comes up on another platform than its parent reports
    an error instead of serving from the wrong device."""
    import multiprocessing as mp

    from repro.live.container import _child_main

    parent, child = mp.Pipe()
    _child_main(child, _container_spec(), 0, "tpu", True)
    msg = parent.recv()
    assert msg[0] == "error" and "parent runs on tpu" in msg[1]
