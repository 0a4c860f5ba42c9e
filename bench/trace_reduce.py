"""Reduce a JAX profiler trace of one window to the benchmark's numbers.

The trace holds the device's own operations (``XLA Ops``) and executables
(``XLA Modules``) on each ``/device:TPU:N`` plane, and the harness's spans
(``window``, ``orchestrator``, ``collect``, ``batcher.step``,
``create_hook``) on the host plane, on one clock. From them:

- ``window_s``: the length of the harness's ``window`` span;
- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
- ``ops`` and ``modules``: device seconds per operation and per executable
  (with its run count);
- ``idle_by_host``: the device's idle time inside the window, each gap given
  to the innermost harness span covering its midpoint (``host_loop`` where
  none does: the harness waiting for the next arrival or event);
- ``breakdown``: the ten operations that took most time and the ten largest
  shares of idle time by what the host was doing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("window", "orchestrator", "collect", "batcher.step",
              "create_hook")
INNERMOST_FIRST = ("batcher.step", "create_hook", "collect", "orchestrator")
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _is_device(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def events(planes) -> Dict:
    """Pull what the reduction reads out of a ``ProfileData``'s planes:
    per device, its op and module events; the host's harness spans. Times
    are seconds."""
    devices, spans = [], []
    for plane in planes:
        if _is_device(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [(re.sub(r"\(\d+\)$", "", e.name),
                              e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events]
            if ops:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events if e.name in HOST_SPANS]
    return {"devices": devices, "spans": spans}


def reduce_events(ev: Dict) -> Optional[Dict]:
    devices, spans = ev["devices"], ev["spans"]
    win = [s for s in spans if s[0] == "window"]
    if not devices or not win:
        return None
    lo, hi = win[0][1], win[0][2]
    busy, ops, mods = [], {}, {}
    for d in devices:
        busy.append(_union(_clip([(a, b) for _, a, b in d["ops"]], lo, hi)))
        for name, a, b in d["ops"]:
            ops[name] = ops.get(name, 0.0) + (b - a)
        for name, a, b in d["modules"]:
            n, t = mods.get(name, (0, 0.0))
            mods[name] = (n + 1, t + (b - a))
    busy_s = sum(sum(b - a for a, b in u) for u in busy) / len(busy)

    # idle gaps of the first device, by what the host was doing; spans of
    # one name never overlap, and the names nest in INNERMOST_FIRST order
    by_name = {n: sorted((a, b) for m, a, b in spans if m == n)
               for n in INNERMOST_FIRST}
    starts = {n: [a for a, _ in v] for n, v in by_name.items()}

    def host_at(t: float) -> str:
        for n in INNERMOST_FIRST:
            i = bisect.bisect_right(starts[n], t) - 1
            if i >= 0 and t < by_name[n][i][1]:
                return n
        return "host_loop"

    idle: Dict[str, float] = {}
    edges = [lo] + [x for a, b in busy[0] for x in (a, b)] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            who = host_at(0.5 * (a + b))
            idle[who] = idle.get(who, 0.0) + (b - a)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": hi - lo, "busy_s": busy_s, "devices": len(devices),
            "ops": ops, "modules": {k: list(v) for k, v in mods.items()},
            "idle_by_host": idle,
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in top_idle]}}


def reduce(trace_dir) -> Optional[Dict]:
    """The reduction of the newest trace under ``trace_dir``; ``None`` when
    there is no trace or no device operation in it."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_events(events(ProfileData.from_file(path).planes))
