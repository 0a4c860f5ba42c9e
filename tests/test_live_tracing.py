"""The serving path's own spans, counters and request stamps: the batcher's
per-step stats and cumulative counters agree, a request's wall stamps come
in order through a live cluster, and a profiler trace holds the spans, on
the CPU with a tiny model."""
import glob
import os
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.core import Cluster, Function, ScalingConfig
from repro.core.monitoring import render_metrics
from repro.core.request import LiveRequest
from repro.live import LiveBackend, LiveFunctionSpec
from repro.serving.engine import ContinuousBatcher, Replica
from repro.serving.exec_cache import ExecutableCache
from repro.simcore import Environment

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace_reduce import HOST_SPANS  # noqa: E402

TINY = get_config("smollm-360m").reduced(
    n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=128)
PROMPTS = [[1, 2, 3, 4, 5], [6], [7, 8, 9], [10, 11]]
COUNTERS = ("steps", "slot_steps", "prompt_tokens", "generated_tokens")


@pytest.fixture(scope="module")
def replica():
    return Replica(TINY, max_seq=64, exec_cache=ExecutableCache())


def _fill(cb):
    for i, p in enumerate(PROMPTS[:3]):
        cb.add_request(p, max_new=2 + i)


def _tokens(cb):
    """Output tokens so far: finished requests' and those still in slots."""
    return sum(len(t) for t in cb.finished.values()) + sum(
        len(s.generated) for s in cb.slots if s.active)


def _counters(cb):
    return {k: getattr(cb, k) for k in COUNTERS}


def _trace(tmp_path, fn):
    """Run ``fn`` under a CPU profiler trace; the host's events as
    ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("engine.", "live."))]


def test_every_step_splits_its_active_slots(replica):
    """Each step's active slots either fed a prompt token and generated
    nothing, or generated one token: the counters move by exactly that."""
    cb = ContinuousBatcher(replica, max_slots=4)
    _fill(cb)
    cb.add_request(PROMPTS[3], max_new=3)
    while any(s.active for s in cb.slots):
        active = sum(s.active for s in cb.slots)
        prompt = sum(s.active and len(s.pending) > 1 for s in cb.slots)
        before, made = _counters(cb), _tokens(cb)
        cb.step()
        d = {k: getattr(cb, k) - before[k] for k in COUNTERS}
        assert d["steps"] == 1 and d["slot_steps"] == active
        assert d["prompt_tokens"] == prompt
        assert d["prompt_tokens"] + d["generated_tokens"] == active
        assert d["generated_tokens"] == _tokens(cb) - made
    assert cb.generated_tokens == sum(len(t) for t in cb.finished.values())
    assert cb.prompt_tokens == sum(len(p) - 1 for p in PROMPTS)


def test_trace_holds_steps_with_stats_and_nested_children(replica, tmp_path):
    cb = ContinuousBatcher(replica, max_slots=4)
    _fill(cb)
    cb.step()                      # compiled before the trace
    before = _counters(cb)
    ev = _trace(tmp_path, lambda: [cb.step() for _ in range(4)])
    steps = [e for e in ev if e[0] == "engine.step"]
    assert len(steps) == 4
    for name, a, b, stats in steps:
        assert stats["slots"] == 4
        assert stats["prompt"] + stats["generated"] == stats["active"]
        kids = sorted((e for e in ev if e[0] != "engine.step"
                       and a <= e[1] and e[2] <= b), key=lambda e: e[1])
        assert [k[0] for k in kids] == ["engine.prepare", "engine.dispatch",
                                       "engine.fetch", "engine.update"]
    # the cumulative counters are the sums of the traced steps' stats
    assert cb.steps - before["steps"] == len(steps)
    for counter, stat in (("slot_steps", "active"),
                          ("prompt_tokens", "prompt"),
                          ("generated_tokens", "generated")):
        assert getattr(cb, counter) - before[counter] == sum(
            s[3][stat] for s in steps)


def _live_cluster(env, lb):
    cl = Cluster(env, n_workers=2, runtime="firecracker", live_backend=lb,
                 sandbox_concurrency=4)
    cl.start()
    leader = cl.control_plane_leader()
    leader.install_function(Function(
        name="lf0", image_url="img://t", port=80,
        scaling=ScalingConfig(stable_window=1.0, panic_window=1.0,
                              scale_to_zero_grace=0.2)))
    for dp in cl.data_planes:
        dp.sync_functions(["lf0"])
    return cl


def _serve(tmp_path=None):
    env = Environment(seed=3)
    lb = LiveBackend(default_spec=LiveFunctionSpec(
        cfg=TINY, max_seq=64, max_slots=4, default_max_new=4),
        exec_cache=ExecutableCache())
    cl = _live_cluster(env, lb)
    invs = []

    def driver(env):
        for p in PROMPTS:
            invs.append(cl.invoke("lf0", 0.01, request=LiveRequest(
                prompt=p, max_new_tokens=3)))
            yield env.timeout(0.001)

    env.process(driver(env), name="driver")
    run = lambda: env.run(until=30.0)                  # noqa: E731
    ev = _trace(tmp_path, run) if tmp_path is not None else run()
    return lb, cl, invs, ev


def test_request_stamps_in_order_through_a_live_cluster():
    lb, cl, invs, _ = _serve()
    assert len(invs) == len(PROMPTS)
    for inv in invs:
        r = inv.request
        assert not inv.failed and len(r.tokens) == 3
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_last \
            <= r.t_collected
    # the start log times each phase of the replica build
    for row in lb.start_log:
        assert row["wall_s"] >= row["params_s"] + row["cache_s"] \
            + row["warm_s"] >= 0
    # the operator's view: counters of the live batchers and of those torn
    # down (scale to zero) alike
    counts = lb.batcher_counts()
    assert lb.teardowns >= 1 and counts["steps"] > 0
    assert counts["prompt_tokens"] + counts["generated_tokens"] \
        == counts["slot_steps"] <= counts["capacity_steps"]
    assert counts["generated_tokens"] == 3 * len(PROMPTS)
    text = render_metrics(cl)
    for family, key in (
            ("dirigent_live_decode_steps_total", "steps"),
            ("dirigent_live_slot_steps_total", "slot_steps"),
            ("dirigent_live_slot_capacity_steps_total", "capacity_steps"),
            ("dirigent_live_prompt_tokens_total", "prompt_tokens"),
            ("dirigent_live_generated_tokens_total", "generated_tokens")):
        assert f"{family} {counts[key]}\n" in text


def test_program_span_names_stay_out_of_the_harness_reduction(tmp_path):
    """The harness's reduction reads its own spans by name: the program's
    spans must not collide with them."""
    *_, ev = _serve(tmp_path)
    names = {e[0] for e in ev}
    assert {"engine.step", "engine.fetch", "live.admit", "live.pump",
            "live.create.params", "live.create.cache",
            "live.create.warm"} <= names
    assert not names & set(HOST_SPANS)
    pumps = [e for e in ev if e[0] == "live.pump"]
    assert all({"sandbox", "rid", "steps"} <= set(e[3]) for e in pumps)
    assert sum(e[3]["steps"] for e in pumps) == sum(
        e[0] == "engine.step" for e in ev)
