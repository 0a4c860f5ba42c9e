"""The program's own spans in a traced run, on the device trace's clock.

The serving path marks its work with ``jax.profiler.TraceAnnotation``
spans: ``engine.step`` and its children ``engine.prepare``,
``engine.dispatch``, ``engine.fetch`` and ``engine.update`` in
``serving/engine.py``, with the step's ``slots``, ``active``, ``prompt`` and
``generated`` as stats; ``live.admit``, ``live.pump`` and
``live.create.*`` in ``live/backend.py``. None of these names is one of
``bench.trace_reduce.HOST_SPANS``, so the harness's own reduction reads what
it read before they existed.

``load(tree, rec)`` reads them from the newest trace under
``<tree>/out/trace/`` (``tree`` is the ``bench`` directory the caller's
reader lives in), once per trace file, clipped to the harness's ``window``
span. It needs no device plane. Where the trace has one, it also writes to
standard error how the device's idle time in the window falls among the
program's spans, and how the device time of the decode executable falls
among the ``jax.named_scope`` parts of ``decode_step``.

On a program that has none of these spans, ``load`` gives a trace with no
program spans, and the readers give nothing.

``stamp_gaps(rec, since, until)`` reads the ``perf_counter`` stamps on each
``LiveRequest`` instead, over the whole window and its drain, less the time
the host spent stopping the profiler (``trace_stop``).
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import re
import sys
import traceback
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench.trace_reduce import _is_device, _union, find_xplane

PREFIXES = ("engine.", "live.")
# a device idle gap goes to the first of these that covers its midpoint:
# spans of one name never overlap, and a step's children nest in the step,
# the step in a pump
INNERMOST_FIRST = ("engine.prepare", "engine.dispatch", "engine.fetch",
                   "engine.update", "engine.step", "live.admit",
                   "live.create.params", "live.create.cache",
                   "live.create.warm", "live.pump")
OUTSIDE = "outside_program"
DECODE_MODULE = "jit_decode_step"
# the named scopes of models/transformer.py decode_step
SCOPES = ("embed", "attn", "kv_write", "mlp", "final_norm", "logits")
UNSCOPED = "unscoped"
TOP = 8


class Span(NamedTuple):
    name: str
    t0: float                        # seconds on the trace's clock
    t1: float
    stats: Dict[str, object]


class ProgramTrace(NamedTuple):
    window: Tuple[float, float]
    spans: List[Span]                # program spans in the window, by start

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def scope_of(op_name: Optional[str]) -> str:
    """The ``decode_step`` scope path in an op's HLO ``op_name``
    (``attn/kv_write``, ``mlp``...); ``unscoped`` where it names none of
    ``SCOPES``."""
    path = [c for c in (op_name or "").split("/") if c in SCOPES]
    return "/".join(path) or UNSCOPED


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """``(field, value)`` of one protobuf message in ``buf[i:end]``; a
    length-delimited value is its ``(start, end)`` in ``buf``."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def tf_ops(buf: bytes) -> Dict[str, str]:
    """Each op of the first device plane of a serialized ``XSpace`` (the
    ``.xplane.pb`` file), by the name its events carry, with its HLO
    ``op_name``: the ``tf_op`` stat of the op's event metadata. JAX's
    ``ProfileData`` gives events their own stats only (on a TPU, times),
    so this reads the file's few metadata fields itself and skips the
    events. Field numbers are those of ``xplane.proto``: an ``XSpace``'s
    planes are 1; an ``XPlane``'s name 2, event metadata 4 and stat
    metadata 5 (map entries of key 1 and value 2); an ``XEventMetadata``'s
    name 2 and stats 5; an ``XStatMetadata``'s name 2; an ``XStat``'s
    metadata id 1, string value 5 and interned-string reference 7."""
    text = lambda span: buf[span[0]:span[1]].decode(errors="replace")  # noqa: E731
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = text(v)
            elif f in (4, 5):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(buf, *entry[2]))
                    stat_names[entry.get(1, 0)] = text(meta.get(2, (0, 0)))
        if not _is_device(name):
            continue
        out = {}
        for ev in events:
            op, path = "", None
            for f, v in _fields(buf, *ev):
                if f == 2:
                    op = text(v)
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        path = text(stat[5])
                    elif 7 in stat:
                        path = stat_names.get(stat[7])
            if path is not None:
                out[op] = path
        return out
    return {}


def events(planes, op_names: Optional[Dict[str, str]] = None) -> Dict:
    """What this module reads from a ``ProfileData``'s planes, with the ops'
    HLO ``op_name``s (``tf_ops``): ``host_spans`` and ``device_events``.
    Times are seconds."""
    planes = list(planes)
    return {"spans": host_spans(planes),
            **device_events(planes, op_names or {})}


def host_spans(planes) -> List[Span]:
    """The host's ``window`` span and the program's spans, with stats."""
    return [Span(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "window" or e.name.startswith(PREFIXES)]


def device_events(planes, op_names: Dict[str, str]) -> Dict:
    """The first device's ops and executables, and each op's scope."""
    ops, modules = [], []
    for plane in planes:
        if not _is_device(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        if "XLA Modules" in lines:
            modules = [(re.sub(r"\(\d+\)$", "", e.name), e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for e in lines["XLA Modules"].events]
        ops = [(e.name, e.start_ns * 1e-9,
                (e.start_ns + e.duration_ns) * 1e-9)
               for e in lines["XLA Ops"].events]
        break
    scopes = {op: scope_of(op_names.get(op)) for op in {o[0] for o in ops}}
    return {"ops": ops, "modules": modules, "scopes": scopes}


def reduce_events(ev: Dict) -> Optional[ProgramTrace]:
    """The program's spans clipped to the ``window`` span; ``None`` where
    the trace has no window."""
    win = [s for s in ev["spans"] if s.name == "window"]
    if not win:
        return None
    lo, hi = win[0].t0, win[0].t1
    spans = sorted((Span(s.name, max(s.t0, lo), min(s.t1, hi), s.stats)
                    for s in ev["spans"]
                    if s.name != "window" and s.t1 > lo and s.t0 < hi),
                   key=lambda s: s.t0)
    return ProgramTrace((lo, hi), spans)


def self_times(ops) -> List[Tuple[str, float, float]]:
    """Each op's start and its own device seconds: its duration less that
    of the ops it contains (a ``while`` contains its body's ops)."""
    out, stack = [], []
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= b - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return [(name, a, own) for name, a, _, own in out]


def idle_by_span(ev: Dict, pt: ProgramTrace) -> Dict[str, float]:
    """The device's idle seconds in the window, each gap given to the
    innermost program span over its midpoint (``outside_program`` where
    none is)."""
    lo, hi = pt.window
    busy = _union([(max(a, lo), min(b, hi)) for _, a, b in ev["ops"]
                   if b > lo and a < hi])
    by_name = {n: [(s.t0, s.t1) for s in pt.named(n)]
               for n in INNERMOST_FIRST}
    starts = {n: [a for a, _ in v] for n, v in by_name.items()}

    def span_at(t: float) -> str:
        for n in INNERMOST_FIRST:
            i = bisect.bisect_right(starts[n], t) - 1
            if i >= 0 and t < by_name[n][i][1]:
                return n
        return OUTSIDE

    idle: Dict[str, float] = {}
    edges = [lo] + [x for a, b in busy for x in (a, b)] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            who = span_at(0.5 * (a + b))
            idle[who] = idle.get(who, 0.0) + (b - a)
    return idle


def decode_by_scope(ev: Dict, pt: ProgramTrace) -> Dict:
    """The decode executable's runs in the window, and its ops' own device
    seconds by ``decode_step`` scope and by op."""
    lo, hi = pt.window
    runs = sorted((a, b) for n, a, b in ev["modules"]
                  if n == DECODE_MODULE and lo <= a < hi)
    starts = [a for a, _ in runs]
    by_scope: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    for name, a, own in self_times(ev["ops"]):
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= runs[i][1]:
            continue
        scope = ev["scopes"].get(name, UNSCOPED)
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        by_op[name] = by_op.get(name, 0.0) + own
    scoped = sum(ev["scopes"].get(op, UNSCOPED) != UNSCOPED for op in by_op)
    return {"runs": len(runs), "by_scope": by_scope, "by_op": by_op,
            "scoped_ops": scoped}


def report(ev: Dict, pt: ProgramTrace, out=None) -> None:
    """Where the device's idle time and the decode executable's device
    time went, for a trace with a device plane; to standard error unless
    ``out`` is given."""
    if not ev["ops"]:
        return
    out = out or sys.stderr
    idle = idle_by_span(ev, pt)
    print("device idle in the window by program span: " + ", ".join(
        f"{n} {v:.6f} s" for n, v in sorted(idle.items(),
                                           key=lambda kv: -kv[1])),
          file=out)
    dec = decode_by_scope(ev, pt)
    if not dec["runs"]:
        return
    n = dec["runs"]
    total = sum(dec["by_scope"].values())
    if not dec["scoped_ops"]:
        print(f"warning: no op of {DECODE_MODULE} names a scope of "
              f"decode_step: its executable came from a compile cache filled "
              f"by a build without them (the cache key leaves out op "
              f"metadata), so the split by scope below is not this "
              f"program's", file=out)
    print(f"{DECODE_MODULE}: {n} runs in the window, {1e3 * total / n:.6f} "
          f"ms of ops' own device time a run ({dec['scoped_ops']} of its "
          f"{len(dec['by_op'])} ops in a scope); by scope: " + ", ".join(
              f"{s} {1e3 * v / n:.6f} ms"
              for s, v in sorted(dec["by_scope"].items(),
                                 key=lambda kv: -kv[1])), file=out)
    top = sorted(dec["by_op"].items(), key=lambda kv: -kv[1])[:TOP]
    print(f"{DECODE_MODULE} top ops by own time a run: " + ", ".join(
        f"{op} [{ev['scopes'].get(op, UNSCOPED)}] {1e3 * v / n:.6f} ms"
        for op, v in top), file=out)


_LOADED: Dict[str, Optional[ProgramTrace]] = {}


def newest_xplane(tree: Path) -> Optional[str]:
    found = [f for d in glob.glob(os.path.join(str(tree), "out", "trace", "*"))
             if (f := find_xplane(d))]
    return max(found, key=os.path.getmtime) if found else None


def load(tree, rec=None) -> Optional[ProgramTrace]:
    """The program's spans in the newest trace under ``<tree>/out/trace/``;
    ``None`` when there is none. With the harness's ``rec``, a trace whose
    window is not the one ``rec.trace`` was reduced from is an error."""
    path = newest_xplane(Path(tree))
    if path is None:
        return None
    if path not in _LOADED:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        pt = _LOADED[path] = reduce_events({"spans": host_spans(data.planes)})
        if pt is not None:
            try:
                report({"spans": pt.spans, **device_events(
                    data.planes, tf_ops(Path(path).read_bytes()))}, pt)
            except Exception:
                # the report is a diagnostic: it never costs the metrics
                traceback.print_exc()
    pt = _LOADED[path]
    if pt is not None:
        check_window(pt, getattr(rec, "trace", None), path)
    return pt


def check_window(pt: ProgramTrace, trace: Optional[Dict], path: str) -> None:
    """Raise unless ``pt`` has the window of ``trace``, the harness's
    reduction of the run's trace (where there is one): both were read from
    the same file."""
    lo, hi = pt.window
    if trace is not None and not math.isclose(
            hi - lo, trace["window_s"], rel_tol=0.0, abs_tol=1e-9):
        raise RuntimeError(f"{path}: window {hi - lo} s, the reduced "
                           f"trace's {trace['window_s']} s")


def trace_stop(rec) -> Optional[Tuple[float, float]]:
    """Where the host stopped the profiler and wrote the trace, in window
    offsets: from the end of the harness's ``trace`` span, recorded just
    before ``stop_trace``, to the first step or span the harness recorded
    after it. ``None`` in an untraced run, or where nothing followed."""
    ends = [b for _, b in rec.spans.get("trace", ())]
    if not ends:
        return None
    end = max(ends)
    later = [t for s in rec.steps for t in (s.t0, s.t1) if t > end]
    later += [t for name, spans in rec.spans.items() if name != "trace"
              for a, b in spans for t in (a, b) if t > end]
    return (end, min(later)) if later else None


_SAID: set = set()


def stamp_gaps(rec, since: str, until: str) -> List[float]:
    """``until - since`` over the ``LiveRequest`` stamps of the window's
    requests that finished, in the window or in the drain after it, where
    the program set both. In a traced run the host holds everything while
    it stops the profiler (``trace_stop``, tens of seconds on a chip), and
    the window's first wave ends in the drain; that hold is taken out of
    each gap that spans it, so the gaps are the program's."""
    pairs = []
    for r in rec.reqs:
        req = getattr(r.inv, "request", None)
        if r.done is None or r.inv.failed or req is None:
            continue
        a, b = getattr(req, since, None), getattr(req, until, None)
        if a is not None and b is not None:
            pairs.append((a, b))
    stop = trace_stop(rec) if pairs else None
    if stop is None:
        return [b - a for a, b in pairs]
    # the stamps are perf_counter readings; a request's submission offset
    # was read just before its t_submit, so the least difference is the
    # window's start on the stamps' clock
    t0 = min(r.inv.request.t_submit - r.submitted for r in rec.reqs
             if getattr(getattr(r.inv, "request", None), "t_submit", None)
             is not None)
    lo, hi = t0 + stop[0], t0 + stop[1]
    out = [(b - a) - max(0.0, min(b, hi) - max(a, lo)) for a, b in pairs]
    if id(rec) not in _SAID:
        _SAID.add(id(rec))
        print(f"stopping the profiler held the host {hi - lo:.6f} s, from "
              f"{stop[0]:.6f} s into the window: taken out of the request "
              f"waits that span it", file=sys.stderr)
    return out
