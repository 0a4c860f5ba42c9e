"""Churn-at-scale sweep: scheduler throughput across (workers x creation rate).

The paper's headline numbers are churn numbers — 2500 sandbox creations/s on
93 nodes, graceful degradation at 5000 workers (C1/C9). This benchmark keeps
the perf trajectory honest on two axes at once:

  * simulated sandbox throughput / latency per grid cell (the modeled
    system), and
  * wall-clock simulator events/s per cell (is Python the bottleneck, or
    the model?);

plus a placer microbenchmark that pits the incremental score index against
the seed's brute-force full rescan at 5000 nodes — the asymptotic fix this
sweep exists to protect,

plus a control-plane shard sweep at the 5000-worker regime: the same churn
workload against ``cp_shards`` in {1, 2, 4, ...}. With one shard the modeled
scale lock caps creations at ~2700/s (C1) and 5000 workers' heartbeats eat
into that budget (C9); the sweep records how modeled creation throughput,
tail latency and accumulated lock-convoy time move as the CP is partitioned
(core/control_plane.py),

plus a skewed-popularity sweep at the same regime: function popularity is
Zipf (the Azure-trace shape, Shahrad et al.) and each function's traffic
arrives as periodic cold bursts, so sandbox-creation load concentrates on
the shards that own the popular functions. The static ``stable_hash % N``
partition convoys on the hot shard; the sweep records shards 1→8 with the
load-adaptive rebalancer + work-stealing spill off vs on
(``cp_rebalance_enabled``, core/control_plane.py),

plus a *single dominant function* sweep (``single_hot_fn``): one function
carries ~80% of the creation load — the irreducible hotspot whole-function
rebalancing cannot fix — recorded at shards 4/8 with per-function creation
sharding (``cp_fn_split_enabled``, fn→shard-set ownership) off vs on; the
split must cut the hot shard's lock wait and the post-warmup tail at equal
shard count with total creations unchanged,

plus a live-mode smoke cell (``--live-smoke`` runs it alone): the same churn
shape against workers whose ``create_hook`` builds a *real* replica payload,
so wall-clock creation throughput covers actual sandbox construction work,
not only DES bookkeeping (ROADMAP "live-mode churn bench"),

plus a multi-data-plane sweep (``multi_dp_sweep``, ``--multi-dp`` runs it
alone): the ``single_hot_fn`` workload pushed *past* the ~1400 conn/s
per-DP port ceiling (C5: 28k ephemeral ports / 20 s TIME_WAIT) that forced
the hot-fn sweep to stay at rate 1500. The above-ceiling cell is recorded
with the steering/connection knobs off (port exhaustion: the blowup PR 5
could not record), then with fn→DP-set spreading (``dp_spread_enabled``),
with invoke-path connection reuse (``dp_conn_reuse``), and with both +
the coalesced CP→DP endpoint flush (``cp_ep_flush_coalesce``) — the fixed
cells must land p99 back in the below-ceiling reference's regime.

Emits ``BENCH_churn.json`` (schema in docs/benchmarks.md): results, a
``meta.provenance`` block (git SHA, python/numpy/jax versions, CPU count,
timestamp) so wall-clock numbers are comparable across PRs, and a
``perf_trajectory`` list (preserved across re-runs) holding before/after
wall-clock records of deliberate perf changes. ``--smoke`` runs a
seconds-scale subset (CI).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import time

import numpy as np

if __package__ in (None, ""):          # `python benchmarks/churn_scale.py`
    import os
    import sys
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)                        # the benchmarks package
    sys.path.insert(0, os.path.join(_root, "src"))   # repro itself

from benchmarks.common import (
    SWEEP_SCALING, latency_stats, make_dirigent, preload_functions,
    run_open_loop,
)
from repro.core.placement import Placer, make_placer
from repro.simcore import Environment

REQ_CPU, REQ_MEM = 100, 128         # SWEEP_SCALING request footprint


def bench_provenance() -> dict:
    """Run provenance for ``meta``: enough to judge whether two recorded
    wall-clock numbers are comparable (same tree? same machine class?)."""
    prov = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "python": platform.python_version(),
        "numpy": np.__version__,
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
    }
    try:
        prov["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        prov["git_sha"] = None
    try:
        import jax
        prov["jax"] = jax.__version__
    except Exception:                 # noqa: BLE001 — jax is optional here
        prov["jax"] = None
    return prov


def placer_microbench(n_nodes: int, n_ops: int, use_index: bool,
                      policy: str = "balanced", churn: bool = True) -> dict:
    """Wall-clock placements/s on a steady-churn workload: fill a warm pool,
    then alternate release/place so every op hits a non-trivial index state."""
    if policy == "partitioned":
        placer = make_placer("partitioned", use_index=use_index)
    else:
        placer = Placer(policy, use_index=use_index)
    for wid in range(n_nodes):
        placer.add_node(wid, 4000, 8192)
    warm = min(n_nodes * 4, n_ops)
    placed = []
    for _ in range(warm):
        wid = placer.place(REQ_CPU, REQ_MEM)
        if wid is not None:
            placed.append(wid)
    t0 = time.perf_counter()
    for i in range(n_ops):
        if churn and placed:
            placer.release(placed[i % len(placed)], REQ_CPU, REQ_MEM)
        wid = placer.place(REQ_CPU, REQ_MEM)
        if churn and placed and wid is not None:
            placed[i % len(placed)] = wid
    wall = time.perf_counter() - t0
    return {"n_nodes": n_nodes, "n_ops": n_ops, "policy": policy,
            "use_index": use_index, "wall_s": round(wall, 4),
            "places_per_s": round(n_ops / wall, 1)}


def churn_point(n_workers: int, rate: float, duration: float,
                seed: int = 71, placement_policy: str = "balanced",
                cp_shards: int = 1, hb_cohort: bool = False,
                vector_windows: bool = False,
                group_commit: bool = False) -> dict:
    """One grid cell: the scalability.py cold-start churn workload, with
    wall-clock accounting alongside the simulated latency stats.

    ``hb_cohort`` turns on the cohort heartbeat wheel (same-deadline beats
    snap to a shared grid and pop as one event) and ``vector_windows`` the
    array-backed metric windows — the two decision-identical fast paths that
    make the 50k-worker cell wall-clock feasible (tests/test_vectorized.py
    pins both against their scalar references). ``group_commit`` turns on
    WAL group commit (``persist_group_commit``), without which the 100k-cell
    boot alone is O(n_workers) serialized fsyncs ≈ 2+ minutes of sim time."""
    env = Environment(seed=seed)
    kw = {}
    if hb_cohort:
        from repro.core.costmodel import DEFAULT_COSTS
        kw["hb_cohort_quantum"] = \
            DEFAULT_COSTS.dirigent.worker_hb_cohort_quantum
    cl = make_dirigent(env, n_workers=n_workers, runtime="firecracker",
                       placement_policy=placement_policy,
                       cp_shards=cp_shards,
                       cp_vector_windows=vector_windows,
                       persist_group_commit=group_commit, **kw)
    plan = [(i / rate, f"f{i}", 0.05) for i in range(int(rate * duration))]
    preload_functions(cl, [p[1] for p in plan], SWEEP_SCALING)
    ev0, t0 = env.events_processed, time.perf_counter()
    invs = run_open_loop(env, cl, plan, until_extra=30.0)
    wall = time.perf_counter() - t0
    events = env.events_processed - ev0
    stats = latency_stats(invs, "e2e_latency")
    leader = cl.control_plane_leader()
    return {
        "workers": n_workers, "rate": rate, "duration": duration,
        "policy": placement_policy, "cp_shards": cp_shards,
        "hb_cohort": hb_cohort, "vector_windows": vector_windows,
        "group_commit": group_commit,
        "group_commits": cl.store.group_commits,
        "events_per_creation": round(
            (env.events_processed - ev0)
            / max(cl.collector.sandbox_creations, 1), 1),
        "wall_s": round(wall, 3), "sim_s": round(env.now, 3),
        "events": events, "events_per_wall_s": round(events / wall, 1),
        "creations": cl.collector.sandbox_creations,
        "creations_per_wall_s": round(cl.collector.sandbox_creations / wall, 1),
        # wall-clock columns answer the separate "is Python the bottleneck"
        # question; this is the modeled ceiling
        "creations_per_sim_s": creations_per_sim_s(cl.collector),
        "reconciles": cl.collector.reconciles,
        "lock_wait_sim_s": (round(sum(s.lock_wait_s for s in leader.shards), 4)
                            if leader else None),
        "done": stats["done"], "total": stats["total"],
        "p50_ms": round(stats["p50"] * 1e3, 3),
        "p99_ms": round(stats["p99"] * 1e3, 3),
    }


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-s)
    return w / w.sum()


def creations_per_sim_s(collector):
    """Modeled creation throughput over the window creations actually
    happened in — the C1 ceiling the CP shards raise."""
    ts = [t for t, k, _ in collector.events if k == "sandbox-created"]
    span = (ts[-1] - ts[0]) if len(ts) > 1 else 0.0
    return round((len(ts) - 1) / span, 1) if span > 0 else None


def skew_point(n_workers: int, rate: float, duration: float,
               n_functions: int = 128, zipf_s: float = 1.2,
               burst_period: float = 4.0, seed: int = 91,
               cp_shards: int = 1, rebalance: bool = False,
               weights: "np.ndarray | None" = None,
               names_prefix: str = "z",
               fn_split: bool = False,
               fn_split_max_shards: "int | None" = None,
               n_data_planes: int = 3,
               dp_spread: bool = False,
               conn_reuse: bool = False,
               ep_coalesce: bool = False,
               costs=None) -> dict:
    """One skew cell: Zipf-popularity function mix, unison cold bursts.

    Function *i* owns a Zipf(s) share of the offered rate and receives it as
    one *instantaneous* burst per ``burst_period`` (the timer-triggered
    unison-burst shape of the Azure trace §5.3, all functions in phase).
    The period is long enough for every function to scale fully back to zero
    between waves (grace 0.2 s + the 2 s autoscale tick + drain), so each
    wave is a pure cold scale-up of burst size: per-shard sandbox-creation
    load is proportional to the popularity share the shard's functions hold
    — maximally skewed under static hashing — and the wave drains at the
    shard's scale-lock rate, which is exactly what couples the hot shard's
    lock convoy into request latency. Latency stats skip the first two waves
    (warm-up: the rebalancer needs a wave of signal before it reacts).
    Records the per-shard lock-convoy split plus the rebalancer /
    work-stealing counters next to the usual churn accounting.

    ``weights`` overrides the Zipf popularity vector (the ``single_hot_fn``
    cell passes one function ~80% of the load); ``fn_split`` enables the
    per-function creation sharding escalation (``cp_fn_split_enabled``)."""
    env = Environment(seed=seed)
    cl = make_dirigent(env, n_workers=n_workers, runtime="firecracker",
                       cp_shards=cp_shards, cp_rebalance_enabled=rebalance,
                       cp_fn_split_enabled=fn_split,
                       cp_fn_split_max_shards=fn_split_max_shards,
                       n_data_planes=n_data_planes,
                       dp_spread_enabled=dp_spread,
                       dp_conn_reuse=conn_reuse,
                       cp_ep_flush_coalesce=ep_coalesce,
                       costs=costs)
    if weights is None:
        weights = zipf_weights(n_functions, zipf_s)
    n_functions = len(weights)
    names = [f"{names_prefix}{i}" for i in range(n_functions)]
    per_period = rate * burst_period
    plan = []
    for i, name in enumerate(names):
        burst = int(round(weights[i] * per_period))
        if burst == 0:
            continue
        t = 0.05
        while t < duration:
            plan.extend((t, name, 0.1) for _ in range(burst))
            t += burst_period
    plan.sort()
    preload_functions(cl, names, SWEEP_SCALING)
    ev0, t0 = env.events_processed, time.perf_counter()
    # plan times are offsets from *traffic start*, which is env.now after
    # the O(n_workers)-fsyncs boot — the warmup cut must use the same origin
    # or it silently no-ops (or over-cuts) at large n_workers
    traffic_t0 = env.now
    invs = run_open_loop(env, cl, plan, until_extra=15.0)
    wall = time.perf_counter() - t0
    warmup = min(2 * burst_period, duration / 2)
    stats = latency_stats([i for i in invs
                           if i.arrival - traffic_t0 >= warmup],
                          "e2e_latency")
    leader = cl.control_plane_leader()
    lock_waits = sorted((s.lock_wait_s for s in leader.shards), reverse=True)
    return {
        "workers": n_workers, "rate": rate, "duration": duration,
        "n_functions": n_functions, "zipf_s": zipf_s,
        "burst_period": burst_period, "warmup": warmup,
        "cp_shards": cp_shards,
        "rebalance": rebalance, "fn_split": fn_split, "offered": len(plan),
        "wall_s": round(wall, 3), "sim_s": round(env.now, 3),
        "events": env.events_processed - ev0,
        "creations": cl.collector.sandbox_creations,
        "creations_per_sim_s": creations_per_sim_s(cl.collector),
        "fn_migrations": cl.collector.fn_migrations,
        "fn_splits": cl.collector.fn_splits,
        "fn_merges": cl.collector.fn_merges,
        "steals": cl.collector.steals,
        "steal_probes": cl.collector.steal_probes,
        "lock_wait_sim_s": round(sum(lock_waits), 4),
        "lock_wait_hottest_shard_s": round(lock_waits[0], 4),
        "n_data_planes": n_data_planes,
        "dp_spread": dp_spread, "dp_conn_reuse": conn_reuse,
        "ep_coalesce": ep_coalesce,
        "dp_spread_fns": len(cl.fn_dp_table),
        "conn_hits": sum(dp.conn_hits for dp in cl.data_planes),
        "conn_misses": sum(dp.conn_misses for dp in cl.data_planes),
        "done": stats["done"], "total": stats["total"],
        "p50_ms": round(stats["p50"] * 1e3, 3),
        "p99_ms": round(stats["p99"] * 1e3, 3),
        "mean_ms": round(stats["mean"] * 1e3, 3),
    }


def single_hot_fn_point(n_workers: int, rate: float, duration: float,
                        n_functions: int = 64, hot_share: float = 0.8,
                        burst_period: float = 4.0, seed: int = 93,
                        cp_shards: int = 4, rebalance: bool = True,
                        fn_split: bool = False,
                        fn_split_max_shards: "int | None" = None,
                        **dp_kw) -> dict:
    """One *dominant-function* cell: a single function carries ``hot_share``
    (~80%) of the offered creation load, the rest spread uniformly over the
    other functions — the irreducible-hotspot regime whole-function
    rebalancing cannot fix (moving the hot function just relocates its
    convoy). This is the cell per-function creation sharding
    (``cp_fn_split_enabled``) exists to improve: at equal shard count,
    splitting the hot function across a shard-set must cut the hot shard's
    lock wait and the post-warmup tail while total creations stay equal."""
    weights = np.full(n_functions, (1.0 - hot_share) / (n_functions - 1))
    weights[0] = hot_share
    cell = skew_point(n_workers, rate, duration, burst_period=burst_period,
                      seed=seed, cp_shards=cp_shards, rebalance=rebalance,
                      weights=weights, names_prefix="h", fn_split=fn_split,
                      fn_split_max_shards=fn_split_max_shards, **dp_kw)
    cell["hot_share"] = hot_share
    cell["fn_split_max_shards"] = fn_split_max_shards
    return cell


def _print_multi_dp(cell: dict) -> None:
    print(f"multi-dp workers={cell['workers']} rate={cell['rate']:.0f} "
          f"dps={cell['n_data_planes']} "
          f"spread={'on' if cell['dp_spread'] else 'off'} "
          f"reuse={'on' if cell['dp_conn_reuse'] else 'off'} "
          f"coalesce={'on' if cell['ep_coalesce'] else 'off'}: "
          f"spread_fns={cell['dp_spread_fns']} "
          f"conn_hits={cell['conn_hits']}, "
          f"p50={cell['p50_ms']:.1f}ms p99={cell['p99_ms']:.1f}ms "
          f"done={cell['done']}/{cell['total']}", flush=True)


def multi_dp_cells(smoke: bool = False) -> list:
    """The ``multi_dp_sweep`` cells: the single-hot-fn workload at and past
    the per-DP port ceiling, knobs off vs on.

    Full cells sit at rate 2500 (hot fn ≈ 2000 conn/s): each 4 s wave opens
    an 8000-connection burst whose ports ride 20 s of TIME_WAIT, so one DP's
    28k-port pool carries ~40k held ports by wave 5 — exhaustion mid-run
    (this is the cell the PR 5 sweep could not record). Spread across a
    width-3 DP-set the same load holds ~13k ports per DP; with connection
    reuse the ports held scale with *concurrent* requests, not request
    volume, and scale-to-zero teardown closes conns server-side (no
    TIME_WAIT accumulation). ``fn_split`` stays on so the CP's scale lock
    is not the binding constraint in any cell — what moves is the DP side.

    Smoke cells shrink the regime instead of the arithmetic: a 3k-port pool
    makes a 500-worker/rate-1000 cell (hot fn ≈ 3200-conn waves) exhaust a
    single DP just as surely, in seconds."""
    if smoke:
        import dataclasses
        from repro.core.costmodel import CostModel, DEFAULT_COSTS
        small = CostModel(dirigent=dataclasses.replace(
            DEFAULT_COSTS.dirigent, dp_port_pool=3000))
        base = dict(n_workers=500, rate=1000.0, duration=8.0, cp_shards=4,
                    rebalance=False, fn_split=True, costs=small)
        return [
            dict(base),
            dict(base, dp_spread=True, ep_coalesce=True),
            dict(base, conn_reuse=True),
        ]
    base = dict(n_workers=5000, duration=20.0, cp_shards=4,
                rebalance=False, fn_split=True)
    return [
        # below-ceiling reference: the regime PR 5 recorded
        dict(base, rate=1500.0),
        # above the ~1400 conn/s ceiling, knobs off: port exhaustion
        dict(base, rate=2500.0),
        # the two independent fixes, then everything on
        dict(base, rate=2500.0, dp_spread=True, ep_coalesce=True),
        dict(base, rate=2500.0, conn_reuse=True),
        dict(base, rate=2500.0, dp_spread=True, conn_reuse=True,
             ep_coalesce=True),
    ]


def run_multi_dp_sweep(smoke: bool = False) -> list:
    cells = []
    for kw in multi_dp_cells(smoke):
        cell = single_hot_fn_point(**kw)
        cells.append(cell)
        _print_multi_dp(cell)
    return cells


def run_multi_dp(out: str = "BENCH_churn.json", smoke: bool = False) -> dict:
    """``--multi-dp``: run only the multi-DP sweep and merge it into the
    existing out-file (preserving the recorded sweeps)."""
    cells = run_multi_dp_sweep(smoke)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    result["multi_dp_sweep"] = {"provenance": bench_provenance(),
                                "cells": cells}
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def failover_point(n_workers: int, cp_shards: int, rate: float = 1000.0,
                   duration: float = 8.0, kill_at: float = 4.0,
                   incremental: bool = True, seed: int = 77,
                   recovery_window: float = 2.0, n_hot: int = 16,
                   group_commit: bool = False, checkpoint: bool = False,
                   checkpoint_period: float = 1.5,
                   read_per_record: float = 0.0) -> dict:
    """One ``failover_scale`` cell: leader killed mid-churn, with a live
    fn→shard-set split and a whole-function migration in flight.

    Workload: a cold-churn plan (one never-seen function per arrival, so
    every post-recovery arrival is a creation demand) plus one capped hot
    function (``max_scale=n_hot``, no scale-to-zero) providing standing
    traffic whose replica count is pinned — its creation count cannot vary
    with recovery timing, so total creations must be EQUAL between the
    serial and incremental runs of a pair (the acceptance invariant).

    Churn arrivals pause for 0.3 s before the kill so no creation is in
    flight at the kill instant: the first ``sandbox-created`` event after
    the kill is then a creation *initiated* by the recovering leader, and
    time-to-first-creation cleanly decomposes into (election + replay-to-
    first-admission + sandbox boot). Both modes pay the same election and
    boot; what the sweep measures is the admission term — full-snapshot
    replay (serial) vs first-shard-unit completion (incremental).

    Pre-kill state the replay must handle: the hot function split across a
    shard-set (persisted override), one churn function migrated off its
    hash home (persisted override), and a second migration spawned 100 µs
    before the kill — mid-quiesce, never persisted, must roll back.

    The 100k extension: ``group_commit`` makes the boot feasible (O(batches)
    of fsync sim-time), ``read_per_record`` makes a full ``worker/`` prefix
    scan honestly record-count-proportional, and ``checkpoint`` gives the
    recovering leader a compacted snapshot + post-checkpoint delta instead
    of that scan — the off-vs-on pair at equal seed isolates what
    checkpointed recovery buys (creations must stay bit-equal)."""
    from repro.core.costmodel import DEFAULT_COSTS
    env = Environment(seed=seed)
    cl = make_dirigent(
        env, n_workers=n_workers, runtime="firecracker",
        cp_shards=cp_shards, enable_ha_sim=True,
        cp_incremental_recovery=incremental,
        cp_vector_windows=True,
        cp_rebalance_enabled=cp_shards > 1,
        cp_rebalance_period=1e9,          # handoffs driven explicitly below
        cp_fn_split_enabled=cp_shards > 1,
        hb_cohort_quantum=DEFAULT_COSTS.dirigent.worker_hb_cohort_quantum,
        persist_group_commit=group_commit,
        persist_read_per_record=read_per_record,
        cp_checkpoint_enabled=checkpoint,
        cp_checkpoint_period=checkpoint_period)
    gap = 0.3                              # pre-kill churn quiet period
    n_churn = int(rate * (duration - gap))
    churn_names = [f"c{i}" for i in range(n_churn)]
    preload_functions(cl, churn_names, SWEEP_SCALING, persist=True)
    preload_functions(cl, ["hot"],
                      dict(SWEEP_SCALING, stable_window=4.0,
                           scale_to_zero_grace=300.0, max_scale=n_hot),
                      persist=True)
    t0 = env.now
    invs = []
    hot_rate = 200.0
    plan = [(j / hot_rate, "hot", 0.1)
            for j in range(int(hot_rate * duration))]
    t, i = 0.0, 0
    while i < n_churn:
        if not (kill_at - gap <= t < kill_at):
            plan.append((t, churn_names[i], 0.05))
            i += 1
        t += 1.0 / rate
    plan.sort()

    def driver(env):
        t_prev = 0.0
        for t, fn, et in plan:
            if t > t_prev:
                yield env.timeout(t - t_prev)
                t_prev = t
            invs.append(cl.invoke(fn, exec_time=et))

    ev0, w0 = env.events_processed, time.perf_counter()
    env.process(driver(env), name="failover-driver")
    leader = cl.control_plane_leader()
    if cp_shards > 1:
        env.run(until=t0 + 2.0)
        # live split + one persisted migration for the replay to keep
        members = tuple(range(min(4, cp_shards)))
        env.process(leader._split_function("hot", members),
                    name="force-split")
        src = leader._fn_shard_id("c0")
        dst = (src + 1) % cp_shards
        env.process(leader._migrate_functions(
            leader.shards[src], leader.shards[dst], ["c0"]), name="force-mig")
        env.run(until=t0 + kill_at - 1e-4)
        # a second migration spawned mid-quiesce: in flight at the kill,
        # never persisted — replay must land c1 back on its hash home
        src2 = leader._fn_shard_id("c1")
        env.process(leader._migrate_functions(
            leader.shards[src2], leader.shards[(src2 + 1) % cp_shards],
            ["c1"]), name="inflight-mig")
    env.run(until=t0 + kill_at)
    t_kill = env.now
    pre_creations = cl.collector.sandbox_creations
    # what the recovering leader will actually see: snapshot epoch + the
    # post-checkpoint delta it replays per record instead of the full prefix
    ckpt_epoch_at_kill = cl.store.checkpoint_epoch
    ckpt_delta_at_kill = len(cl.store._ckpt_delta)
    cl.fail_control_plane_leader()
    env.run(until=t0 + duration + 30.0)
    wall = time.perf_counter() - w0

    col = cl.collector
    ttfc = col.first_event_at("sandbox-created", after=t_kill)
    recovered = col.first_event_at("cp-recovered", after=t_kill)
    shard_ts = col.event_times("cp-shard-recovered", after=t_kill)
    win = col.window_sched_latencies(t_kill, t_kill + recovery_window)
    stats = latency_stats(invs, "e2e_latency")
    return {
        "workers": n_workers, "cp_shards": cp_shards, "rate": rate,
        "duration": duration, "kill_at": kill_at,
        "mode": "incremental" if (incremental and cp_shards > 1)
                else "serial",
        "group_commit": group_commit, "checkpoint": checkpoint,
        "read_per_record": read_per_record,
        "checkpoint_epoch_at_kill": ckpt_epoch_at_kill,
        "checkpoint_delta_at_kill": ckpt_delta_at_kill,
        "wall_s": round(wall, 3),
        "events": env.events_processed - ev0,
        "creations": col.sandbox_creations,
        "creations_pre_kill": pre_creations,
        "fn_splits": col.fn_splits,
        "time_to_first_creation_s": (round(ttfc - t_kill, 6)
                                     if ttfc is not None else None),
        "recovered_s": (round(recovered - t_kill, 6)
                        if recovered is not None else None),
        "first_shard_admitted_s": (round(min(shard_ts) - t_kill, 6)
                                   if shard_ts else None),
        "shards_recovered": len(shard_ts),
        "recovery_window_s": recovery_window,
        "recovery_window_n": int(win.size),
        "recovery_window_p50_ms": (round(float(np.percentile(win, 50)) * 1e3,
                                         3) if win.size else None),
        "recovery_window_p99_ms": (round(float(np.percentile(win, 99)) * 1e3,
                                         3) if win.size else None),
        "done": stats["done"], "total": stats["total"],
        "p99_ms": round(stats["p99"] * 1e3, 3),
    }


def _print_failover(cell: dict) -> None:
    fs = cell["first_shard_admitted_s"]
    print(f"failover workers={cell['workers']} shards={cell['cp_shards']} "
          f"mode={cell['mode']} "
          f"ckpt={'on' if cell.get('checkpoint') else 'off'}: "
          f"ttfc={cell['time_to_first_creation_s']}s "
          f"recovered={cell['recovered_s']}s "
          f"first_shard={'-' if fs is None else f'{fs}s'} "
          f"win_p99={cell['recovery_window_p99_ms']}ms "
          f"creations={cell['creations']} "
          f"done={cell['done']}/{cell['total']}", flush=True)


def failover_cells(smoke: bool = False) -> list:
    """(workers, shards, incremental) rows. Shard count 1 has no per-shard
    units to parallelize — ``cp_incremental_recovery`` falls back to the
    serial path — so it is recorded once, as the serial anchor."""
    if smoke:
        return [(500, 4, False), (500, 4, True)]
    rows = []
    for w in (5000, 20_000, 50_000):
        rows.append((w, 1, False))
        for s in (4, 8):
            rows.append((w, s, False))
            rows.append((w, s, True))
    return rows


def run_failover_sweep(smoke: bool = False) -> list:
    cells = []
    for w, s, inc in failover_cells(smoke):
        cell = failover_point(w, s, incremental=inc)
        cells.append(cell)
        _print_failover(cell)
    if not smoke:
        for kw in failover_100k_cells():
            cell = failover_point(**kw)
            cells.append(cell)
            _print_failover(cell)
    return cells


def run_failover(out: str = "BENCH_churn.json", smoke: bool = False) -> dict:
    """``--failover``: run only the failover_scale sweep and merge it into
    the existing out-file (preserving the recorded sweeps)."""
    cells = run_failover_sweep(smoke)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    result["failover_scale"] = {"provenance": bench_provenance(),
                                "cells": cells}
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def boot_point(n_workers: int, group_commit: bool, cp_shards: int = 8,
               seed: int = 71, probe: bool = True) -> dict:
    """One ``boot_scale`` cell: cold cluster → every worker registered and
    heartbeating, the O(n_workers)-serialized-fsyncs path group commit
    exists to cut. ``store_crc`` digests the full store (keys AND values, in
    insertion order), so the off/on cells at equal worker count prove from
    the JSON alone that bulk registration landed the identical log; the
    probe cell is a small post-boot workload whose done/creation counts
    must match across the pair (equivalent post-boot behaviour)."""
    import zlib as _zlib
    from repro.core.costmodel import DEFAULT_COSTS
    env = Environment(seed=seed)
    w0 = time.perf_counter()
    cl = make_dirigent(
        env, n_workers=n_workers, runtime="firecracker",
        cp_shards=cp_shards, cp_vector_windows=True,
        hb_cohort_quantum=DEFAULT_COSTS.dirigent.worker_hb_cohort_quantum,
        persist_group_commit=group_commit)
    boot_sim, boot_wall = env.now, time.perf_counter() - w0
    store = cl.store
    crc = 0
    for k, v in store.data.items():
        crc = _zlib.crc32(v, _zlib.crc32(k.encode(), crc))
    cell = {
        "workers": n_workers, "cp_shards": cp_shards,
        "group_commit": group_commit,
        "boot_sim_s": round(boot_sim, 6),
        "boot_wall_s": round(boot_wall, 3),
        "write_count": store.write_count,
        "group_commits": store.group_commits,
        "group_commit_writes": store.group_commit_writes,
        "store_records": len(store.data),
        "store_crc": crc,
    }
    if probe:
        preload_functions(cl, ["probe"], SWEEP_SCALING)
        t0 = env.now
        invs = [cl.invoke("probe", exec_time=0.02) for _ in range(32)]
        env.run(until=t0 + 5.0)
        cell["probe_done"] = sum(1 for i in invs
                                 if i.t_done > 0 and not i.failed)
        cell["probe_creations"] = cl.collector.sandbox_creations
    return cell


def _print_boot(cell: dict) -> None:
    print(f"boot workers={cell['workers']} "
          f"gc={'on' if cell['group_commit'] else 'off'}: "
          f"sim={cell['boot_sim_s']:.3f}s wall={cell['boot_wall_s']:.1f}s "
          f"writes={cell['write_count']} commits={cell['group_commits']} "
          f"crc={cell['store_crc']} "
          f"probe={cell.get('probe_done')}/{cell.get('probe_creations')}",
          flush=True)


def run_boot_scale_sweep(smoke: bool = False) -> list:
    sizes = (2000,) if smoke else (20_000, 50_000, 100_000)
    cells = []
    for n in sizes:
        for gc in (False, True):
            cell = boot_point(n, group_commit=gc)
            cells.append(cell)
            _print_boot(cell)
    return cells


def run_boot_scale(out: str = "BENCH_churn.json",
                   smoke: bool = False) -> dict:
    """``--boot-scale``: run only the boot sweep (workers × group-commit
    off/on) and merge it into the existing out-file."""
    cells = run_boot_scale_sweep(smoke)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    result["boot_scale"] = {"provenance": bench_provenance(), "cells": cells}
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def failover_100k_cells() -> list:
    """The checkpointed-recovery pair: 100k workers, 8 shards, incremental
    recovery, group-commit boot, honest record-count-proportional prefix
    scans — identical except ``checkpoint``, so the delta-replay term is the
    only thing that moves (and creations must stay bit-equal)."""
    base = dict(n_workers=100_000, cp_shards=8, incremental=True,
                group_commit=True, read_per_record=1e-6)
    return [dict(base, checkpoint=False), dict(base, checkpoint=True)]


def run_failover_100k(out: str = "BENCH_churn.json") -> dict:
    """``--failover-100k``: run only the 100k checkpoint-off/on pair and
    append it to the recorded ``failover_scale`` cells (replacing any prior
    100k rows rather than re-running the whole sweep)."""
    cells = []
    for kw in failover_100k_cells():
        cell = failover_point(**kw)
        cells.append(cell)
        _print_failover(cell)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    section = result.setdefault("failover_scale", {"cells": []})
    section["cells"] = [c for c in section.get("cells", [])
                        if c["workers"] < 100_000] + cells
    section["provenance_100k"] = bench_provenance()
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def run_scale100k(out: str = "BENCH_churn.json") -> dict:
    """``--scale-100k``: the 100k-worker churn cells (cohort heartbeats +
    vector windows + group-commit boot, sharded CP), merged into the
    existing out-file."""
    cells = [
        churn_point(100_000, 1000, 4.0, cp_shards=8, hb_cohort=True,
                    vector_windows=True, group_commit=True),
        churn_point(100_000, 2500, 4.0, cp_shards=8, hb_cohort=True,
                    vector_windows=True, group_commit=True),
    ]
    for cell in cells:
        print(f"workers={cell['workers']} rate={cell['rate']} "
              f"gc={'on' if cell['group_commit'] else 'off'}: "
              f"{cell['events_per_wall_s']:.0f} ev/s wall, "
              f"{cell['events_per_creation']} events/creation, "
              f"p99={cell['p99_ms']:.1f}ms "
              f"done={cell['done']}/{cell['total']}", flush=True)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    result["scale_100k"] = {"provenance": bench_provenance(), "cells": cells}
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def run_scale50k(out: str = "BENCH_churn.json") -> dict:
    """``--scale-50k``: the 50k-worker churn cells (cohort heartbeats +
    vector windows on, plus a cohort-off companion at 20k for the
    events/creation comparison), merged into the existing out-file."""
    cells = [
        churn_point(20_000, 1000, 4.0, hb_cohort=True, vector_windows=True),
        churn_point(50_000, 1000, 4.0, hb_cohort=True, vector_windows=True),
    ]
    for cell in cells:
        print(f"workers={cell['workers']} rate={cell['rate']} "
              f"cohort={'on' if cell['hb_cohort'] else 'off'}: "
              f"{cell['events_per_wall_s']:.0f} ev/s wall, "
              f"{cell['events_per_creation']} events/creation, "
              f"p99={cell['p99_ms']:.1f}ms "
              f"done={cell['done']}/{cell['total']}", flush=True)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    result["scale_50k"] = {"provenance": bench_provenance(), "cells": cells}
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def live_smoke_point(n_workers: int = 8, n_functions: int = 16,
                     rate: float = 50.0, duration: float = 2.0,
                     seed: int = 7, replica_dim: int = 96) -> dict:
    """Live-mode churn smoke: a small workers×rate cell where every sandbox
    creation runs a *real* ``create_hook`` (allocate + warm a small replica —
    matmul standing in for snapshot-restore/model-load work), so wall-clock
    creation throughput includes genuine payload construction next to the
    DES numbers (ROADMAP "live-mode churn bench"). Teardown of a live
    sandbox reclaims its replica through the worker's ``teardown_hook``
    (symmetric with ``create_hook``), so churn exercises build *and*
    reclaim — the cell asserts zero leaked replicas."""
    env = Environment(seed=seed)
    replicas: dict = {}
    hook_wall = [0.0]

    def create_replica(sandbox):
        t0 = time.perf_counter()
        rng = np.random.default_rng(sandbox.sandbox_id)
        w = rng.standard_normal((replica_dim, replica_dim))
        w = w @ w.T                    # "warm-up" compute, like a real restore
        replicas[sandbox.sandbox_id] = w
        hook_wall[0] += time.perf_counter() - t0

    def teardown_replica(sandbox_id, drain=True):
        # the kill path owns the reclaim (no post-run sweep): kill_sandbox /
        # fail_node call this for every sandbox they dismantle
        replicas.pop(sandbox_id, None)

    cl = make_dirigent(env, n_workers=n_workers, runtime="firecracker",
                       create_hook=create_replica,
                       teardown_hook=teardown_replica)
    plan = [(i / rate, f"lf{i % n_functions}", 0.02)
            for i in range(int(rate * duration))]
    preload_functions(cl, [p[1] for p in plan], SWEEP_SCALING)
    ev0, t0 = env.events_processed, time.perf_counter()
    invs = run_open_loop(env, cl, plan, until_extra=10.0)
    wall = time.perf_counter() - t0
    # every replica still held must belong to a live sandbox: the teardown
    # hook reclaimed the rest as the autoscaler scaled down
    live_ids = {sid for w in cl.workers.values() for sid in w.sandboxes}
    leaked = [s for s in replicas if s not in live_ids]
    assert not leaked, f"teardown_hook leaked {len(leaked)} replicas"
    stats = latency_stats(invs, "e2e_latency")
    creations = cl.collector.sandbox_creations
    return {
        "workers": n_workers, "rate": rate, "duration": duration,
        "n_functions": n_functions, "replica_dim": replica_dim,
        "wall_s": round(wall, 3), "sim_s": round(env.now, 3),
        "events": env.events_processed - ev0,
        "creations": creations,
        "creations_per_wall_s": round(creations / wall, 1),
        "create_hook_wall_s": round(hook_wall[0], 4),
        "create_hook_ms_mean": round(1e3 * hook_wall[0] / max(creations, 1), 3),
        "live_replicas": len(replicas),
        "leaked_replicas": len(leaked),
        "done": stats["done"], "total": stats["total"],
        "p50_ms": round(stats["p50"] * 1e3, 3),
        "p99_ms": round(stats["p99"] * 1e3, 3),
    }


def _print_live_smoke(cell: dict) -> None:
    print(f"live-smoke workers={cell['workers']} rate={cell['rate']:.0f}: "
          f"{cell['creations_per_wall_s']:.0f} creations/s wall "
          f"(hook {cell['create_hook_ms_mean']:.2f} ms/creation), "
          f"p50={cell['p50_ms']:.1f}ms p99={cell['p99_ms']:.1f}ms "
          f"done={cell['done']}/{cell['total']}", flush=True)


def run_live_smoke(out: str = "BENCH_churn.json") -> dict:
    """``--live-smoke``: run the live-mode churn cell plus one real-invoke
    live cell (tiny truncated smollm; payload executed end-to-end through
    CP -> DP -> worker -> batcher) and merge both into the out-file. This
    is the CI leg: seconds-scale, wall-clock numbers recorded but never
    asserted on (timing is machine-dependent); the *functional* bits —
    zero leaked replicas, every completed invoke carrying real tokens —
    are asserted."""
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = live_smoke_point()
    _print_live_smoke(cell)
    real = live_grid_point(4, 20.0, 1.0, n_functions=2)
    real.pop("_start_log")
    real.pop("_invoke_walls")
    assert real["done"] > 0 and real["tokens"] > 0, \
        "live smoke executed no real payloads"
    cell["real_invoke"] = real
    print(f"live real-invoke: done={real['done']}/{real['total']} "
          f"tokens={real['tokens']} "
          f"(cold {real['cold_create_ms_median']}ms / warm "
          f"{real['warm_create_ms_median']}ms)", flush=True)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    # this cell's provenance rides inside the cell: the file-level
    # meta.provenance keeps describing the run that produced the sweeps
    cell["provenance"] = bench_provenance()
    result["live_smoke"] = cell
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return cell


# -- live execution mode (real JAX payloads; ISSUE 10) ------------------------

def _live_spec(mode: str = "process", max_slots: int = 4,
               max_seq: int = 64, max_new: int = 8):
    """Tiny truncated smollm config every live cell shares (CPU-feasible:
    ~1-2 s cold compile, ~5 ms warm replica build)."""
    from repro.configs import get_config
    from repro.live import LiveFunctionSpec

    cfg = get_config("smollm-360m").reduced(
        n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=128)
    return LiveFunctionSpec(cfg=cfg, mode=mode, max_seq=max_seq,
                            max_slots=max_slots, default_max_new=max_new)


def live_cold_warm_point(n_warm: int = 4) -> dict:
    """Cold vs warm process-mode sandbox creation — the shared-executable-
    cache headline (acceptance: warm >= 10x faster than cold). A fresh
    ``ExecutableCache``: the first creation builds the decode executable
    (compile, or a load from the persistent compile cache), the rest reuse
    it."""
    from repro.core.abstractions import Sandbox
    from repro.live import LiveBackend
    from repro.serving.exec_cache import ExecutableCache

    lb = LiveBackend(default_spec=_live_spec("process"),
                     exec_cache=ExecutableCache())
    try:
        for i in range(1 + n_warm):
            sb = Sandbox(sandbox_id=i + 1, function_name="lf",
                         ip=(10, 0, 0, 1), port=80, worker_id=0)
            lb.create_hook(sb)
        rows = lb.start_log
        assert rows[0]["cold"] and not any(r["cold"] for r in rows[1:]), \
            "cold/warm split did not land where expected"
        cold = rows[0]["wall_s"]
        warm = float(np.median([r["wall_s"] for r in rows[1:]]))
        return {"mode": "process", "cold_create_s": round(cold, 4),
                "warm_create_s": round(warm, 4),
                "warm_speedup": round(cold / max(warm, 1e-9), 1),
                "n_warm": n_warm,
                "exec_cache": lb.cache_stats()}
    finally:
        lb.close()


def live_container_cold_warm_point() -> dict:
    """Cold vs warm container-mode creation. The first worker runs with the
    persistent compile cache off, so it compiles (cold). The second fills
    the cache, or finds it filled by an earlier run; the third loads its
    executables from it (warm)."""
    from repro.live.container import ContainerSandbox

    spec = _live_spec("container")
    rows = []
    for persistent in (False, True, True):
        t0 = time.perf_counter()
        sb = ContainerSandbox(spec, persistent_cache=persistent)
        rows.append((sb.cold, time.perf_counter() - t0))
        sb.close()
    assert rows[0][0] and not rows[-1][0], \
        "cold/warm split did not land where expected"
    cold, warm = rows[0][1], rows[-1][1]
    return {"mode": "container", "cold_create_s": round(cold, 4),
            "warm_create_s": round(warm, 4),
            "warm_speedup": round(cold / max(warm, 1e-9), 1), "n_warm": 1}


def live_grid_point(n_workers: int, rate: float, duration: float,
                    mode: str = "process", n_functions: int = 8,
                    seed: int = 11, max_slots: int = 4) -> dict:
    """One live workers x rate cell: every invocation carries a real
    ``LiveRequest`` executed in the dispatched sandbox's batcher."""
    from repro.core.request import LiveRequest
    from repro.live import LiveBackend
    from repro.serving.exec_cache import ExecutableCache

    env = Environment(seed=seed)
    lb = LiveBackend(default_spec=_live_spec(mode, max_slots=max_slots),
                     exec_cache=ExecutableCache())
    cl = make_dirigent(env, n_workers=n_workers, runtime="firecracker",
                       live_backend=lb, sandbox_concurrency=max_slots)
    plan = [(i / rate, f"lf{i % n_functions}", 0.02)
            for i in range(int(rate * duration))]
    preload_functions(cl, [p[1] for p in plan], SWEEP_SCALING)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(1, 127, size=(len(plan), 4))

    def req_factory(i):
        return LiveRequest(prompt=[int(t) for t in prompts[i]],
                           max_new_tokens=8)

    ev0, t0 = env.events_processed, time.perf_counter()
    invs = run_open_loop(env, cl, plan, until_extra=10.0,
                         request_factory=req_factory)
    wall = time.perf_counter() - t0
    try:
        done = [i for i in invs if i.t_done > 0 and not i.failed]
        executed = [i for i in done if i.request.tokens is not None]
        assert len(executed) == len(done), \
            "completed invocation without real payload execution"
        stats = latency_stats(invs, "e2e_latency")
        inv_walls = sorted(i.request.wall_s for i in executed) or [0.0]
        creations = cl.collector.sandbox_creations
        cold = [r for r in lb.start_log if r["cold"]]
        warm = [r for r in lb.start_log if not r["cold"]]
        return {
            "workers": n_workers, "rate": rate, "duration": duration,
            "mode": mode, "n_functions": n_functions,
            "max_slots": max_slots,
            "wall_s": round(wall, 3), "sim_s": round(env.now, 3),
            "events": env.events_processed - ev0,
            "creations": creations,
            "creations_per_wall_s": round(creations / wall, 1),
            "cold_creates": len(cold), "warm_creates": len(warm),
            "cold_create_ms_median": round(
                1e3 * float(np.median([r["wall_s"] for r in cold])), 2)
            if cold else None,
            "warm_create_ms_median": round(
                1e3 * float(np.median([r["wall_s"] for r in warm])), 2)
            if warm else None,
            "done": stats["done"], "total": stats["total"],
            "p50_ms": round(stats["p50"] * 1e3, 3),
            "p99_ms": round(stats["p99"] * 1e3, 3),
            "invoke_wall_p50_ms": round(
                1e3 * inv_walls[len(inv_walls) // 2], 3),
            "invoke_wall_p99_ms": round(
                1e3 * inv_walls[int(len(inv_walls) * 0.99) - 1], 3),
            "tokens": lb.tokens_total,
            "tokens_per_wall_s": round(lb.tokens_total / wall, 1),
            "batched_invokes": lb.batched_invokes,
            "exec_cache": lb.cache_stats(),
            "_start_log": lb.start_log,
            "_invoke_walls": inv_walls,
        }
    finally:
        lb.close()


def live_azure_slice(n_functions: int = 10, duration: float = 6.0,
                     target_invocations: int = 150, n_workers: int = 16,
                     seed: int = 42) -> dict:
    """Azure-trace slice replayed end-to-end in live mode: the Shahrad-style
    workload shape (Zipf popularity, lognormal exec times, timer + Poisson
    arrivals) with a real ``LiveRequest`` on every invocation."""
    from benchmarks.azure_trace import generate_azure_like_trace
    from repro.core.request import LiveRequest
    from repro.live import LiveBackend
    from repro.serving.exec_cache import ExecutableCache

    trace = generate_azure_like_trace(
        n_functions=n_functions, duration=duration,
        target_invocations=target_invocations, seed=seed,
        timer_fraction=0.2, n_timer_groups=2)
    env = Environment(seed=seed)
    lb = LiveBackend(default_spec=_live_spec("process"),
                     exec_cache=ExecutableCache())
    cl = make_dirigent(env, n_workers=n_workers, runtime="firecracker",
                       live_backend=lb, sandbox_concurrency=4)
    preload_functions(cl, [f.name for f in trace.functions], SWEEP_SCALING)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(1, 127, size=(len(trace.invocations), 4))

    def req_factory(i):
        return LiveRequest(prompt=[int(t) for t in prompts[i]],
                           max_new_tokens=8)

    t0 = time.perf_counter()
    invs = run_open_loop(env, cl, trace.invocations, until_extra=15.0,
                         request_factory=req_factory)
    wall = time.perf_counter() - t0
    try:
        done = [i for i in invs if i.t_done > 0 and not i.failed]
        executed = [i for i in done if i.request.tokens is not None]
        assert len(executed) == len(done), \
            "azure slice: completed invocation without payload execution"
        stats = latency_stats(invs, "e2e_latency")
        return {
            "n_functions": n_functions, "trace_duration": duration,
            "invocations": len(trace.invocations),
            "workers": n_workers,
            "wall_s": round(wall, 3), "sim_s": round(env.now, 3),
            "creations": cl.collector.sandbox_creations,
            "done": stats["done"], "total": stats["total"],
            "real_payloads_executed": len(executed),
            "p50_ms": round(stats["p50"] * 1e3, 3),
            "p99_ms": round(stats["p99"] * 1e3, 3),
            "tokens": lb.tokens_total,
            "batched_invokes": lb.batched_invokes,
            "exec_cache": lb.cache_stats(),
        }
    finally:
        lb.close()


def live_grid_section(smoke: bool = False) -> dict:
    """The live execution sweep — cold/warm creation for both modes, a
    workers x rate grid with real payloads on every invoke, and an
    Azure-trace slice. Per-phase wall measurements are folded into a
    calibrated ``DirigentCosts`` candidate (``costs_candidate``) for DES
    cross-checking."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core.costmodel import live_calibrated_candidate

    enable_compile_cache()
    section: dict = {"provenance": bench_provenance()}
    section["cold_warm"] = [live_cold_warm_point()]
    if smoke:
        pass
    elif jax.default_backend() == "tpu":
        # one process per chip: this process holds it, so a spawned
        # container worker could not run (ROADMAP reach item 6)
        section["container_cells"] = (
            "left out: container mode refuses to start on a TPU backend")
        print(f"live container cells {section['container_cells']}",
              flush=True)
    else:
        section["cold_warm"].append(live_container_cold_warm_point())
    for row in section["cold_warm"]:
        print(f"live cold/warm mode={row['mode']}: "
              f"cold={row['cold_create_s'] * 1e3:.0f}ms "
              f"warm={row['warm_create_s'] * 1e3:.1f}ms "
              f"-> {row['warm_speedup']:.0f}x", flush=True)

    grid = ([(8, 50.0, 2.0)] if smoke
            else [(8, 50.0, 2.0), (16, 100.0, 2.0), (32, 200.0, 2.0)])
    cells, start_log, invoke_walls = [], [], []
    for w, r, d in grid:
        cell = live_grid_point(w, r, d)
        start_log += cell.pop("_start_log")
        invoke_walls += cell.pop("_invoke_walls")
        cells.append(cell)
        print(f"live workers={w} rate={r:.0f}: "
              f"{cell['creations_per_wall_s']:.0f} creations/s wall "
              f"(cold {cell['cold_create_ms_median']}ms / warm "
              f"{cell['warm_create_ms_median']}ms), "
              f"invoke p50={cell['invoke_wall_p50_ms']}ms "
              f"p99={cell['invoke_wall_p99_ms']}ms, "
              f"{cell['tokens_per_wall_s']:.0f} tok/s, "
              f"batched={cell['batched_invokes']}, "
              f"done={cell['done']}/{cell['total']}", flush=True)
    section["grid"] = cells
    section["azure_slice"] = live_azure_slice()
    az = section["azure_slice"]
    print(f"live azure slice: {az['real_payloads_executed']}/{az['total']} "
          f"real invokes, {az['creations']} creations, "
          f"p99={az['p99_ms']:.1f}ms, {az['tokens']} tokens", flush=True)
    # container cold/warm rows feed the candidate too
    for row in section["cold_warm"]:
        start_log.append({"mode": row["mode"], "cold": True,
                          "wall_s": row["cold_create_s"]})
        start_log.append({"mode": row["mode"], "cold": False,
                          "wall_s": row["warm_create_s"]})
    section["costs_candidate"] = live_calibrated_candidate(
        start_log, invoke_walls)
    return section


def run_live_grid(out: str = "BENCH_churn.json",
                  smoke: bool = False) -> dict:
    """``--live-grid``: run the live execution sweep alone and merge it
    into the out-file."""
    section = live_grid_section(smoke=smoke)
    try:
        with open(out) as fh:
            result = json.load(fh)
    except (OSError, ValueError):
        result = {"meta": {"bench": "churn_scale"}}
    result["live_grid"] = section
    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return section


def run_bench(smoke: bool = False, out: str = "BENCH_churn.json") -> dict:
    with open(out, "a"):               # fail on an unwritable path up front,
        pass                           # not after minutes of sweep
    # perf_trajectory records deliberate before/after perf work; it must
    # survive re-runs of the sweep
    try:
        with open(out) as fh:
            trajectory = json.load(fh).get("perf_trajectory", [])
    except (OSError, ValueError):
        trajectory = []
    result = {"meta": {"bench": "churn_scale", "smoke": smoke,
                       "provenance": bench_provenance()},
              "placer_microbench": [], "grid": []}
    if trajectory:
        result["perf_trajectory"] = trajectory

    # -- placer microbench: incremental index vs seed brute-force rescan ----
    micro_nodes = 1000 if smoke else 5000
    micro_ops = 2000 if smoke else 20_000
    brute_ops = 500 if smoke else 2000   # brute is slow; scale its op count
    fast = placer_microbench(micro_nodes, micro_ops, use_index=True)
    brute = placer_microbench(micro_nodes, brute_ops, use_index=False)
    part = placer_microbench(micro_nodes, micro_ops, use_index=True,
                             policy="partitioned")
    speedup = fast["places_per_s"] / brute["places_per_s"]
    result["placer_microbench"] = [fast, brute, part]
    result["placer_index_speedup"] = round(speedup, 1)
    print(f"placer@{micro_nodes}: index {fast['places_per_s']:.0f}/s, "
          f"brute {brute['places_per_s']:.0f}/s, "
          f"partitioned {part['places_per_s']:.0f}/s "
          f"-> {speedup:.1f}x index speedup", flush=True)

    # -- churn grid ---------------------------------------------------------
    # 10k/20k workers joined the grid once the PR 4 event tax made them
    # wall-clock feasible: with heartbeats ~1 event/beat and netcfg timers
    # demand-driven, cost now scales with the *workload*, and these cells
    # record where the next bottleneck bites (see docs/benchmarks.md)
    if smoke:
        grid = [(93, 500, 1.0), (1000, 1000, 1.0)]
    else:
        grid = [(w, r, 4.0)
                for w in (93, 1000, 2500, 5000, 10_000, 20_000)
                for r in (1000, 2500)]
    for n_workers, rate, duration in grid:
        cell = churn_point(n_workers, rate, duration)
        result["grid"].append(cell)
        print(f"workers={n_workers} rate={rate}: "
              f"{cell['events_per_wall_s']:.0f} ev/s wall, "
              f"{cell['creations_per_wall_s']:.0f} creations/s wall, "
              f"p99={cell['p99_ms']:.1f}ms "
              f"done={cell['done']}/{cell['total']}", flush=True)

    # partitioned-placer spot check at the largest scale in the grid
    w, r, d = grid[-1]
    cell = churn_point(w, r, d, placement_policy="partitioned")
    result["grid"].append(cell)
    print(f"workers={w} rate={r} policy=partitioned: "
          f"{cell['events_per_wall_s']:.0f} ev/s wall, "
          f"p99={cell['p99_ms']:.1f}ms done={cell['done']}/{cell['total']}",
          flush=True)

    # -- control-plane shard sweep (the C1/C9 regime) -----------------------
    # one scale-lock's modeled budget is ~2700 creations/s minus the
    # heartbeat tax; drive at and beyond it and watch the shards divide it
    if smoke:
        shard_cells = [(1000, 2000.0, 1.0, s) for s in (1, 4)]
    else:
        # the 20k rows pair with the grid's cp_shards=1 cells: at that
        # worker count the C9 heartbeat tax alone (~53% of one lock)
        # saturates an unsharded CP — sharding is the fix, not an option
        shard_cells = ([(5000, 2500.0, 4.0, s) for s in (1, 2, 4, 8)]
                       + [(5000, 5000.0, 4.0, s) for s in (1, 2, 4)]
                       + [(20_000, 2500.0, 4.0, s) for s in (4, 8)])
    result["cp_shard_sweep"] = []
    for n_workers, rate, duration, s in shard_cells:
        cell = churn_point(n_workers, rate, duration, cp_shards=s)
        result["cp_shard_sweep"].append(cell)
        print(f"workers={n_workers} rate={rate:.0f} cp_shards={s}: "
              f"{cell['creations_per_sim_s']} creations/sim_s, "
              f"lock_wait={cell['lock_wait_sim_s']}s, "
              f"p50={cell['p50_ms']:.1f}ms p99={cell['p99_ms']:.1f}ms "
              f"done={cell['done']}/{cell['total']}", flush=True)

    # -- skewed-popularity sweep (hot-shard regime; rebalance off vs on) ----
    # Zipf mix: static hashing piles the popular functions' creation bursts
    # onto one shard's scale lock; the load-adaptive CP spreads them
    if smoke:
        skew_cells = [(500, 1000.0, 8.0, 1, False),
                      (500, 1000.0, 8.0, 4, False),
                      (500, 1000.0, 8.0, 4, True)]
    else:
        skew_cells = [(5000, 2500.0, 20.0, s, rb)
                      for s in (1, 2, 4, 8) for rb in (False, True)
                      if not (s == 1 and rb)]
    result["skew_sweep"] = []
    for n_workers, rate, duration, s, rb in skew_cells:
        cell = skew_point(n_workers, rate, duration,
                          cp_shards=s, rebalance=rb)
        result["skew_sweep"].append(cell)
        print(f"workers={n_workers} zipf rate={rate:.0f} cp_shards={s} "
              f"rebalance={'on' if rb else 'off'}: "
              f"{cell['creations_per_sim_s']} creations/sim_s, "
              f"hot_lock_wait={cell['lock_wait_hottest_shard_s']}s, "
              f"migrations={cell['fn_migrations']} steals={cell['steals']}, "
              f"p50={cell['p50_ms']:.1f}ms p99={cell['p99_ms']:.1f}ms "
              f"mean={cell['mean_ms']:.1f}ms "
              f"done={cell['done']}/{cell['total']}", flush=True)

    # -- single dominant function (the fn->shard-set regime) ----------------
    # one function carries ~80% of the creation load: whole-function
    # rebalancing cannot fix its shard (static and rebalance-on baselines),
    # per-function creation sharding can (fn_split on) — recorded at equal
    # shard counts so the improvement is attributable to the split alone
    if smoke:
        hot_cells = [(500, 1000.0, 8.0, 4, True, False, None),
                     (500, 1000.0, 8.0, 4, True, True, None)]
    else:
        # rate 1500 (hot fn = 1200 creations/s) keeps the cell in the regime
        # where the CP scale lock is the *binding* constraint: all of one
        # function's dispatches go through one DP (function-hash steering),
        # and a DP's port pool sustains ~1400 conn/s (28k ports / 20s
        # TIME_WAIT, the C5 ceiling) — at hot rates above it the cell would
        # measure port exhaustion, which no CP-side mechanism can fix
        hot_cells = [(5000, 1500.0, 20.0, s, rb, sp, mx)
                     for s, rb, sp, mx in (
                         # static baseline / rebalance-only (ping-pongs the
                         # hotspot) / split-only (the clean off-vs-on pair)
                         # / both escalations together
                         (4, False, False, None),
                         (4, True, False, None),
                         (4, False, True, None),
                         (4, True, True, None),
                         (8, False, False, None),
                         (8, True, False, None),
                         (8, False, True, 8),
                         (8, True, True, 8),
                     )]
    result["single_hot_fn"] = []
    for n_workers, rate, duration, s, rb, sp, mx in hot_cells:
        cell = single_hot_fn_point(n_workers, rate, duration, cp_shards=s,
                                   rebalance=rb, fn_split=sp,
                                   fn_split_max_shards=mx)
        result["single_hot_fn"].append(cell)
        print(f"workers={n_workers} hot80 rate={rate:.0f} cp_shards={s} "
              f"rebalance={'on' if rb else 'off'} "
              f"split={'on' if sp else 'off'}: "
              f"{cell['creations_per_sim_s']} creations/sim_s, "
              f"hot_lock_wait={cell['lock_wait_hottest_shard_s']}s, "
              f"splits={cell['fn_splits']} merges={cell['fn_merges']} "
              f"migrations={cell['fn_migrations']}, "
              f"p50={cell['p50_ms']:.1f}ms p99={cell['p99_ms']:.1f}ms "
              f"done={cell['done']}/{cell['total']}", flush=True)

    # -- multi-data-plane sweep (the C5 port-ceiling regime) ----------------
    result["multi_dp_sweep"] = {"provenance": result["meta"]["provenance"],
                                "cells": run_multi_dp_sweep(smoke)}

    # -- failover at scale (serial vs incremental leader recovery) ----------
    result["failover_scale"] = {"provenance": result["meta"]["provenance"],
                                "cells": run_failover_sweep(smoke)}

    # -- 50k-worker cells (cohort heartbeats + vector windows) --------------
    if not smoke:
        result["scale_50k"] = {
            "provenance": result["meta"]["provenance"],
            "cells": [churn_point(20_000, 1000, 4.0, hb_cohort=True,
                                  vector_windows=True),
                      churn_point(50_000, 1000, 4.0, hb_cohort=True,
                                  vector_windows=True)]}

    # -- live-mode smoke (real create_hook payloads; ROADMAP item) ----------
    result["live_smoke"] = cell = live_smoke_point()
    _print_live_smoke(cell)

    # -- live execution sweep (real JAX payloads on the invoke path) --------
    if not smoke:
        result["live_grid"] = live_grid_section(smoke=False)

    with open(out, "w") as fh:
        json.dump(result, fh, indent=2)
    print(f"wrote {out}", flush=True)
    return result


def run(reporter, quick: bool = True) -> dict:
    """benchmarks/run.py harness adapter (CSV reporter contract)."""
    result = run_bench(smoke=quick)
    for row in result["placer_microbench"]:
        tag = "partitioned" if row["policy"] == "partitioned" else (
            "index" if row["use_index"] else "brute")
        reporter.add(f"churn/placer-{tag}@{row['n_nodes']}",
                     1e6 / max(row["places_per_s"], 1e-9),
                     f"places_per_s={row['places_per_s']}")
    for cell in result["grid"]:
        reporter.add(
            f"churn/workers={cell['workers']}/rate={cell['rate']}"
            + ("" if cell["policy"] == "balanced" else f"/{cell['policy']}"),
            cell["p50_ms"] * 1e3,
            f"p99_ms={cell['p99_ms']};ev_per_wall_s={cell['events_per_wall_s']}")
    for cell in result.get("cp_shard_sweep", []):
        reporter.add(
            f"churn/shards={cell['cp_shards']}/workers={cell['workers']}"
            f"/rate={cell['rate']}",
            cell["p50_ms"] * 1e3,
            f"p99_ms={cell['p99_ms']};"
            f"creations_per_sim_s={cell['creations_per_sim_s']};"
            f"lock_wait_sim_s={cell['lock_wait_sim_s']}")
    for cell in result.get("skew_sweep", []):
        reporter.add(
            f"churn/skew/shards={cell['cp_shards']}"
            f"/rebalance={'on' if cell['rebalance'] else 'off'}",
            cell["p50_ms"] * 1e3,
            f"p99_ms={cell['p99_ms']};"
            f"creations_per_sim_s={cell['creations_per_sim_s']};"
            f"hot_lock_wait_s={cell['lock_wait_hottest_shard_s']};"
            f"migrations={cell['fn_migrations']};steals={cell['steals']}")
    for cell in result.get("single_hot_fn", []):
        reporter.add(
            f"churn/hotfn/shards={cell['cp_shards']}"
            f"/rebalance={'on' if cell['rebalance'] else 'off'}"
            f"/split={'on' if cell['fn_split'] else 'off'}",
            cell["p50_ms"] * 1e3,
            f"p99_ms={cell['p99_ms']};"
            f"hot_lock_wait_s={cell['lock_wait_hottest_shard_s']};"
            f"splits={cell['fn_splits']};merges={cell['fn_merges']}")
    for cell in result.get("failover_scale", {}).get("cells", []):
        ttfc = cell["time_to_first_creation_s"]
        reporter.add(
            f"churn/failover/workers={cell['workers']}"
            f"/shards={cell['cp_shards']}/{cell['mode']}",
            (ttfc or 0.0) * 1e6,
            f"recovered_s={cell['recovered_s']};"
            f"win_p99_ms={cell['recovery_window_p99_ms']};"
            f"creations={cell['creations']}")
    for cell in result.get("multi_dp_sweep", {}).get("cells", []):
        reporter.add(
            f"churn/multidp/rate={cell['rate']:.0f}"
            f"/spread={'on' if cell['dp_spread'] else 'off'}"
            f"/reuse={'on' if cell['dp_conn_reuse'] else 'off'}",
            cell["p50_ms"] * 1e3,
            f"p99_ms={cell['p99_ms']};done={cell['done']};"
            f"spread_fns={cell['dp_spread_fns']};"
            f"conn_hits={cell['conn_hits']}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale subset for CI")
    ap.add_argument("--live-smoke", action="store_true",
                    help="run only the live-mode (create_hook) churn cell "
                         "and merge it into --out")
    ap.add_argument("--live-grid", action="store_true",
                    help="run only the live execution sweep (real JAX "
                         "payloads: cold/warm creation, workers x rate, "
                         "Azure slice) and merge it into --out (honors "
                         "--smoke)")
    ap.add_argument("--multi-dp", action="store_true",
                    help="run only the multi-data-plane sweep and merge it "
                         "into --out (honors --smoke)")
    ap.add_argument("--failover", action="store_true",
                    help="run only the failover_scale sweep (leader killed "
                         "mid-churn; serial vs incremental recovery) and "
                         "merge it into --out (honors --smoke)")
    ap.add_argument("--scale-50k", action="store_true",
                    help="run only the 50k-worker churn cells (cohort "
                         "heartbeats + vector windows) and merge into --out")
    ap.add_argument("--scale-100k", action="store_true",
                    help="run only the 100k-worker churn cells (group-commit "
                         "boot, 8 CP shards) and merge into --out")
    ap.add_argument("--boot-scale", action="store_true",
                    help="run only the boot sweep (workers x group-commit "
                         "off/on) and merge into --out (honors --smoke)")
    ap.add_argument("--failover-100k", action="store_true",
                    help="run only the 100k checkpoint-off/on failover pair "
                         "and append it to the recorded failover_scale cells")
    ap.add_argument("--out", default="BENCH_churn.json")
    args = ap.parse_args()
    if args.live_smoke:
        run_live_smoke(out=args.out)
    elif args.live_grid:
        run_live_grid(out=args.out, smoke=args.smoke)
    elif args.multi_dp:
        run_multi_dp(out=args.out, smoke=args.smoke)
    elif args.failover:
        run_failover(out=args.out, smoke=args.smoke)
    elif args.failover_100k:
        run_failover_100k(out=args.out)
    elif args.scale_50k:
        run_scale50k(out=args.out)
    elif args.scale_100k:
        run_scale100k(out=args.out)
    elif args.boot_scale:
        run_boot_scale(out=args.out, smoke=args.smoke)
    else:
        run_bench(smoke=args.smoke, out=args.out)
