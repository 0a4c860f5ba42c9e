"""One benchmark cell: set-up, a wall-clock window of live invocations, the
correctness check, and the metrics.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file and the payload it names in ``bench/payloads/<payload>.py``
(the program mapping, the reference and the step counts of its
architecture), ``bench/traffic/<mix>.json`` and the generator kind it names
in ``bench/traffic/kinds/<kind>.py``, and one reader per per-layer metric in
``bench/metrics/<metric>.py``.

The window drives ``Cluster.invoke(fn, request=LiveRequest(...))``. The
harness owns a wall clock that starts when warm-up ends. An arrival due at
offset ``d`` is submitted at the first loop turn at or after ``d``; the DES
is advanced with ``env.run(until=...)`` and never ahead of the wall clock, so
modelled delays become real waits and a blocking payload call holds back
everything behind it. A request's latency is the wall time at which the DES
completes it (stamped through the cluster's collector) minus the wall time
at which it was due.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from bench import reference
from bench.gen import Arrival

# a traced run records the first TRACE_SECONDS of its window: enough steps
# for the device's shares, and a trace small enough to write and reduce
# well inside a run's time limit
TRACE_SECONDS = 10.0
# how often the window advances the DES when no arrival is due sooner: the
# modelled hops and boots it waits for are milliseconds long
POLL_S = 0.002


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here (no chip, bad spec)."""


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: Dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


@dataclass
class Cell:
    """What ``BENCHMARK.json`` says about one workload, with its files."""

    root: Path
    workload: Dict
    config: Dict
    payload: object                  # the module bench/payloads/<payload>.py
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    quantile: float                  # of the gaps that logit_gap reads

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        spec_path = root / "BENCHMARK.json"
        if not spec_path.is_file():
            raise BenchError(f"missing {spec_path}")
        spec = json.loads(spec_path.read_text())
        wl = {w["name"]: w for w in spec["workloads"]}.get(name)
        if wl is None:
            raise BenchError(f"no workload {name!r} in {spec_path}")
        cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
        config = json.loads((root / cfg_entry["file"]).read_text())
        quantile = config["reference"].get("quantile", 1.0)
        if (isinstance(quantile, bool)
                or not isinstance(quantile, (int, float))
                or not 0 < quantile <= 1):
            raise BenchError(f"{cfg_entry['file']}: reference.quantile "
                             f"{quantile!r} is not a number in (0, 1]")
        traffic = json.loads(
            (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
        return cls(root=root, workload=wl, config=config,
                   payload=load_payload(root, config), traffic=traffic,
                   end_to_end=[m for m in spec["end_to_end"]
                               if _applies(m, name)],
                   per_layer=[m for m in spec["per_layer"]
                              if _applies(m, name)],
                   quantile=float(quantile))

    def generator(self, functions: List[str], seconds: float):
        """The mix's source of arrivals. Its schedule (sizes and times) is
        drawn from the mix's own ``schedule_seed``, so every ``--seed``
        does the same work at the same times; ``--seed`` draws the token
        ids of the prompts."""
        kind = self.traffic["kind"]
        mod = _load_module(self.root / "bench" / "traffic" / "kinds"
                           / f"{kind}.py", f"bench_kind_{kind}")
        rng = np.random.default_rng(self.traffic["schedule_seed"])
        return mod.make(self.traffic["params"], functions, rng, seconds)

    def reader(self, metric: str):
        return _load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                            "bench_metric_" + metric.replace(".", "_")).read


# -- what a run records ---------------------------------------------------------

@dataclass
class Req:
    arrival: Arrival
    due: float                       # window offset it was due at
    submitted: float                 # window offset it was submitted at
    inv: object = None               # the cluster's Invocation
    done: Optional[float] = None     # window offset the DES completed it
    sandbox: Optional[int] = None    # sandbox id that served it


class Step(NamedTuple):
    """One ``ContinuousBatcher.step``: its wall span, the cache length of
    each active slot before it, and how many tokens it generated (slots
    whose prompt was fed by the end of it)."""

    t0: float
    t1: float
    lens: List[int]
    generated: int


@dataclass
class Record:
    """Everything a per-layer reader may read. Times are seconds on the
    host's ``perf_counter`` relative to the window's start."""

    seconds: float                   # the measured window's length
    arch: Dict = field(default_factory=dict)
    payload: object = None           # the configuration's payload module
    peaks: Optional[Dict] = None
    reqs: List[Req] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    spans: Dict[str, List[tuple]] = field(default_factory=dict)
    creations: List[Dict] = field(default_factory=list)   # start_log rows
    trace: Optional[Dict] = None     # bench.trace_reduce output

    def span(self, name: str, t0: float, t1: float) -> None:
        self.spans.setdefault(name, []).append((t0, t1))

    def in_window(self, t: float) -> bool:
        return 0.0 <= t < self.seconds

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.in_window(s.t0)]

    def traced_steps(self) -> List[Step]:
        """The steps inside the traced part of the window."""
        return [s for s in self.steps
                if any(a <= s.t0 and s.t1 <= b
                       for a, b in self.spans.get("trace", ()))]

    def traced_seconds(self) -> float:
        return sum(b - a for a, b in self.spans.get("trace", ()))


class Harness:
    """Builds the configuration's cluster and wraps the calls into each
    layer with the benchmark's own spans."""

    def __init__(self, cell: Cell, seed: int, trace_dir: Optional[Path]):
        import jax

        from repro.configs import get_config
        from repro.core import Cluster, Function, ScalingConfig
        from repro.live import LiveBackend, LiveFunctionSpec
        from repro.simcore import Environment

        self.jax = jax
        self.cell, self.seed = cell, seed
        cfg = cell.config
        self.arch = arch_of(cfg, cell.payload)
        try:
            self.cfg = cell.payload.program_config(get_config(cfg["arch"]),
                                                   cfg)
        except ValueError as e:
            raise BenchError(f"payload {cfg['payload']!r}: {e}") from None
        cl = cfg["cluster"]
        spec = LiveFunctionSpec(cfg=self.cfg, max_seq=cl["max_seq"],
                                max_slots=cl["slots"])
        self.functions = [f["name"] for f in cfg["functions"]]
        self.backend = LiveBackend(specs={f: spec for f in self.functions})
        # the DES's modelled delays (hops, boots) come from the
        # configuration's own seed, so that every run models the same cluster
        self.env = Environment(seed=cl["des_seed"])
        self.cluster = Cluster(self.env, n_workers=cl["workers"],
                               runtime=cl["runtime"],
                               live_backend=self.backend,
                               sandbox_concurrency=cl["slots"])
        self.cluster.start()
        for f in cfg["functions"]:
            self.cluster.register_sync(Function(
                name=f["name"], image_url=f"registry://{cfg['arch']}",
                port=9000, scaling=ScalingConfig(**f["scaling"])))
        self.trace_dir = trace_dir
        self.t0 = time.perf_counter()    # re-based when the window opens
        self.rec = Record(seconds=0.0, arch=self.arch,
                          payload=cell.payload)
        self.by_inv: Dict[int, Req] = {}
        self.by_request: Dict[int, int] = {}      # id(LiveRequest) -> sandbox
        self.source = None
        self._win = None                 # the traced window's annotation
        self._trace_end = math.inf
        self._wrap()

    # -- spans around the calls into each layer -------------------------------
    def _now(self) -> float:
        return time.perf_counter() - self.t0

    def _annotate(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    def _wrap(self) -> None:
        b, rec = self.backend, self.rec
        create, collect, admit = b.create_hook, b.collect, b.admit
        done = self.cluster.collector.done

        def create_hook(sandbox):
            t = self._now()
            with self._annotate("create_hook"):
                create(sandbox)
            rec.span("create_hook", t, self._now())
            row = dict(b.start_log[-1])
            row["t"] = t
            rec.creations.append(row)
            rt = b.sandboxes.get(sandbox.sandbox_id)
            if rt is not None and hasattr(rt, "batcher"):
                rt.batcher.step = self._wrap_step(rt.batcher)

        def collect_(ticket):
            t = self._now()
            with self._annotate("collect"):
                out = collect(ticket)
            rec.span("collect", t, self._now())
            return out

        def admit_(sandbox_id, req):
            self.by_request[id(req)] = sandbox_id
            return admit(sandbox_id, req)

        def done_(inv):
            r = self.by_inv.get(inv.inv_id)
            if r is not None and r.done is None:
                r.done = self._now()
                if self.source is not None:
                    self.source.on_done(r.arrival, r.done)
            done(inv)

        b.create_hook = create_hook
        b.collect = collect_
        b.admit = admit_
        for w in self.cluster.workers.values():
            w.create_hook = create_hook
        self.cluster.collector.done = done_

    def _wrap_step(self, batcher):
        rec = self.rec

        def step_():
            active = [s for s in batcher.slots if s.active]
            gen = sum(1 for s in active if len(s.pending) <= 1)
            t = self._now()
            with self._annotate("batcher.step"):
                out = type(batcher).step(batcher)
            rec.steps.append(Step(t, self._now(), [s.length for s in active],
                                  gen))
            # a pump can hold the host for a whole wave: end the trace on
            # time from here too
            self._end_trace_at(self._trace_end)
            return out
        return step_

    # -- traffic -------------------------------------------------------------
    def prompt(self, rng, n: int) -> List[int]:
        return rng.integers(1, self.arch["vocab_size"], size=n).tolist()

    def submit(self, a: Arrival, rng, now: float) -> Req:
        from repro.core.request import LiveRequest

        r = Req(arrival=a, due=a.due, submitted=now)
        r.inv = self.cluster.invoke(
            a.function, exec_time=0.0,
            request=LiveRequest(prompt=self.prompt(rng, a.prompt_len),
                                max_new_tokens=a.max_new))
        self.by_inv[r.inv.inv_id] = r
        return r

    def prewarm(self) -> None:
        """Create each function's ``prewarm`` replicas and run one request
        through each, unpaced: the decode shape, the argmax and the replica
        build are compiled or loaded from the cache here, in set-up."""
        rng = np.random.default_rng([self.seed, 2])
        warm = []
        for f in self.cell.config["functions"]:
            for _ in range(f.get("prewarm", 0)):
                warm.append(self.submit(Arrival(0.0, f["name"], 8, 2), rng,
                                        0.0))
        deadline = time.perf_counter() + 1200
        while any(r.done is None for r in warm):
            if time.perf_counter() > deadline:
                raise BenchError("warm-up requests did not finish")
            self.env.run(until=self.env.now + 1.0)
        bad = [r for r in warm if r.inv.failed]
        if bad:
            raise BenchError(f"warm-up request failed: "
                             f"{bad[0].inv.failure_reason}")
        want = sum(f.get("prewarm", 0) for f in self.cell.config["functions"])
        if len(self.backend.sandboxes) != want:
            raise BenchError(f"{len(self.backend.sandboxes)} replicas after "
                             f"warm-up, configuration asks for {want}")
        self.by_inv.clear()
        self.rec.steps.clear()
        self.rec.spans.clear()
        self.rec.creations.clear()

    def window(self, seconds: float) -> Dict:
        """Offer the mix for ``seconds`` of wall time, then let what is due
        drain up to the configuration's cap. Returns the generator's
        lateness."""
        env, rec = self.env, self.rec
        rec.seconds = seconds
        self.source = self.cell.generator(self.functions, seconds)
        rng = np.random.default_rng([self.seed, 3])
        cap = seconds + float(self.cell.config["drain_cap_s"])
        lateness: List[float] = []
        if self.trace_dir is not None:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no per-call Python events
            opts.host_tracer_level = 1        # keeps the harness's spans
            self.jax.profiler.start_trace(str(self.trace_dir),
                                          profiler_options=opts)
            self._win = self.jax.profiler.TraceAnnotation("window")
            self._win.__enter__()
            self._trace_end = min(seconds, TRACE_SECONDS)
        self.t0 = time.perf_counter()
        v0 = env.now
        while True:
            now = self._now()
            # every arrival is due inside the window; one the host could not
            # send in time (blocked in a payload call) is sent late, not lost
            for a in self.source.pop_due(now):
                rec.reqs.append(self.submit(a, rng, now))
                lateness.append(now - a.due)
            self._end_trace_at(self._trace_end)
            t = self._now()
            with self._annotate("orchestrator"):
                env.run(until=v0 + now)
            rec.span("orchestrator", t, self._now())
            now = self._now()
            # done once every arrival due in the window has been sent (a
            # payload call may block the host past the close) and answered
            if (now >= seconds and self.source.next_due() >= seconds
                    and all(r.done is not None for r in rec.reqs)):
                break
            if now >= cap:
                # what the host never got to send counts as unfinished
                for a in self.source.pop_due(seconds):
                    rec.reqs.append(self.submit(a, rng, now))
                    lateness.append(now - a.due)
                break
            # the DES's next event is not known from outside: poll it at
            # POLL_S, and wake for the next arrival
            nxt = now + POLL_S
            if now < seconds:
                nxt = min(nxt, self.source.next_due(), seconds)
            wait = min(nxt, cap) - self._now()
            if wait > 0:
                time.sleep(wait)
        self._end_trace_at(0.0)
        return {"p50_ms": 1e3 * statistics.median(lateness) if lateness
                else 0.0,
                "max_ms": 1e3 * max(lateness, default=0.0)}

    def _end_trace_at(self, t: float) -> None:
        """Stop the profiler once the window has run ``t`` seconds. Spans
        still open then (the pump's ``collect``, its ``orchestrator``) are
        left out of the trace; the steps inside them are in it."""
        if self._win is None or self._now() < t:
            return
        self._win.__exit__(None, None, None)
        self._win = None
        self.rec.span("trace", 0.0, self._now())
        self.jax.profiler.stop_trace()

    def close(self) -> None:
        self.backend.close()


# -- configuration -----------------------------------------------------------------

def load_payload(root: Path, cfg: Dict):
    """The payload module that the configuration file names, loaded from
    ``bench/payloads/`` of the checkout at ``root``."""
    name = cfg.get("payload")
    if name is None:
        raise BenchError(f"configuration {cfg.get('name')!r} names no "
                         f"payload (the key \"payload\")")
    mod = _load_module(root / "bench" / "payloads" / f"{name}.py",
                       f"bench_payload_{name}")
    if "vocab_size" not in mod.KEYS:
        raise BenchError(f"payload {name!r} does not read vocab_size")
    return mod


def arch_of(cfg: Dict, payload) -> Dict:
    """The payload's sizes and the served dtype, as the reference and the
    step counts read them."""
    return dict({k: cfg[k] for k in payload.KEYS}, dtype=cfg["dtype"])


# -- the run ---------------------------------------------------------------------------

def percentile(xs: List[float], p: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), p)) if xs else None


def end_to_end(rec: Record, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric the harness knows, from the wall clock."""
    due = [r for r in rec.reqs if r.done is not None and not r.inv.failed]
    lat = [1e3 * (r.done - r.due) for r in due]
    cold = [1e3 * (r.done - r.due) for r in due if r.inv.cold]
    out = {"setup_s": setup_s,
           "invoke_p50_ms": percentile(lat, 50),
           "invoke_p95_ms": percentile(lat, 95),
           "cold_invoke_p50_ms": percentile(cold, 50),
           "tokens_per_s": sum(s.generated for s in rec.window_steps())
           / rec.seconds}
    return {k: v for k, v in out.items() if v is not None}


def sample_for_reference(rec: Record, seed: int, tokens: int) -> List[Dict]:
    """Requests to check, drawn from the seed: the longest finished request
    and then others in seeded order until ``tokens`` served tokens. Each
    names the seed of the replica that served it: the live backend seeds a
    replica's weights with its sandbox id."""
    ok = [r for r in rec.reqs
          if r.done is not None and not r.inv.failed and r.sandbox is not None
          and r.inv.request.tokens]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r.arrival.prompt_len
                                     + len(r.inv.request.tokens)))
    rng = np.random.default_rng([seed, 4])
    picked, n = [longest], len(longest.inv.request.tokens)
    for i in rng.permutation(len(ok)):
        if n >= tokens:
            break
        if ok[i] is not longest:
            picked.append(ok[i])
            n += len(ok[i].inv.request.tokens)
    return [{"seed": r.sandbox, "prompt": r.inv.request.prompt,
             "tokens": list(r.inv.request.tokens)} for r in picked]


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False,
        rehearsal: bool = False) -> Dict:
    """Run one cell and return the result line's object. Raises
    ``BenchError`` where it cannot run here.

    ``control`` puts the control in the program's place for the check: the
    tokens that an fp8 pass of the reference puts first, at the positions of
    the sampled requests, are judged by the same checks, so ``correct``
    must come out false (``bench/run.py --control 1``; the benchmark's own
    runs never set it). ``rehearsal`` runs on whatever JAX finds, with no
    persistent compile cache: the CPU rehearsals in ``bench/tests``."""
    cell = Cell.load(root, workload)
    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if not rehearsal and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {dev.platform} "
                         f"({dev.device_kind})")
    if len(devices) < cell.workload["chips"]:
        raise BenchError(f"{len(devices)} devices, the cell asks for "
                         f"{cell.workload['chips']}")
    if not rehearsal:
        enable_compile_cache()
        # the replica build is made of sub-second programs: cache them all
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if dev.device_kind not in peaks:
        raise BenchError(f"no peaks for device kind {dev.device_kind!r} in "
                         f"bench/peaks.json")

    trace_dir = root / "bench" / "out" / "trace" / workload
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    h = Harness(cell, seed, trace_dir if trace else None)
    h.prewarm()
    setup_s = time.perf_counter() - t_start
    late = h.window(seconds)
    print(f"generator lateness: p50 {late['p50_ms']:.3f} ms, "
          f"max {late['max_ms']:.3f} ms", file=sys.stderr)
    rec = h.rec
    rec.peaks = peaks[dev.device_kind]
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices[:cell.workload["chips"]])

    for r in rec.reqs:
        if r.inv.request is not None:
            r.sandbox = h.by_request.get(id(r.inv.request))
    attempted = len(rec.reqs)
    unfinished = [r for r in rec.reqs if r.done is None]
    failed = [r for r in rec.reqs if r.done is not None and r.inv.failed]
    short = [r for r in rec.reqs if r.done is not None and not r.inv.failed
             and len(r.inv.request.tokens or ()) != r.arrival.max_new]
    window_creations = [c for c in rec.creations if rec.in_window(c["t"])]
    steps = rec.window_steps()
    done_in = [r for r in rec.reqs if r.done is not None
               and r.done <= seconds]
    print(f"window: {attempted} requests due, {len(done_in)} done in the "
          f"window, {len(unfinished)} unfinished at the "
          f"{cell.config['drain_cap_s']} s drain cap, "
          f"{len(window_creations)} replica creations, "
          f"{h.backend.teardowns} teardowns", file=sys.stderr)
    if steps:
        walls = sorted(s.t1 - s.t0 for s in steps)
        print(f"batcher: {len(steps)} steps, median "
              f"{1e3 * walls[len(walls) // 2]:.3f} ms, "
              f"{sum(len(s.lens) for s in steps) / len(steps):.2f} active "
              f"slots per step, {sum(s.generated for s in steps)} tokens "
              f"generated, busy {sum(walls):.3f} s of {seconds} s", file=sys.stderr)
    e2e = end_to_end(rec, setup_s)
    ref_cfg = cell.config["reference"]
    samples = sample_for_reference(rec, seed, ref_cfg["tokens"])
    h.close()
    del h
    gc.collect()          # the wrapped hooks hold the replicas in cycles
    metrics = {}
    if trace:
        from bench import trace_reduce
        rec.trace = trace_reduce.reduce(trace_dir)
        for m in cell.per_layer:
            v = cell.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    t_ref = time.perf_counter()
    ref = reference.check(samples, cell.payload, rec.arch,
                          cell.config["dtype"], control=control,
                          quantile=cell.quantile) \
        if samples else {"logit_gap": None, "tokens_checked": 0}
    print(f"reference: {len(samples)} requests, {ref['tokens_checked']} "
          f"served tokens, {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    for who in ("program", "control"):
        if who in ref:
            d = ref[who]
            qs = ", ".join(f"q{q} {v!r}" for q, v in d["quantiles"].items())
            print(f"gaps of the {who}: judged at quantile {cell.quantile!r}; "
                  f"widest {d['widest']!r}, share_off {d['share_off']!r}, "
                  f"{qs}", file=sys.stderr)
    if control:
        print(f"control: the program's own logit_gap {ref['logit_gap']}, "
              f"the control's {ref.get('control_logit_gap')}",
              file=sys.stderr)
        ref["logit_gap"] = ref.get("control_logit_gap")
    checks = {
        "failed": {"value": len(failed), "limit": 0},
        "unfinished": {"value": len(unfinished), "limit": 0},
        "short": {"value": len(short), "limit": 0},
        "tokens_checked": {"value": ref["tokens_checked"],
                           "limit": ref_cfg["tokens"], "at_least": True},
        "logit_gap": {"value": ref["logit_gap"], "limit": ref_cfg["limit"]},
    }
    correct = all(c["value"] is not None and (
        c["value"] >= c["limit"] if c.get("at_least")
        else c["value"] <= c["limit"]) for c in checks.values())
    for k, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {k}: {c['value']} (limit {rel} {c['limit']})", file=sys.stderr)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": attempted,
           "failed": len(failed) + len(unfinished) + len(short),
           "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        out["breakdown"] = rec.trace["breakdown"]
    out["checks"] = {k: [c["value"], c["limit"]] for k, c in checks.items()}
    return out
