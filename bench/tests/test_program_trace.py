"""``bench/program_trace.py`` and the six readers of the program's own
spans and stamps: on a hand-made trace whose answers are counted by hand,
and on the traced CPU rehearsal of ``llm.saturate``, where the program's
count of active slots must equal the harness's own."""
import json
import time
from types import SimpleNamespace as NS

import pytest

from bench import harness, program_trace
from bench.program_trace import Span
from bench.tests.conftest import REPO

NEW = {"slot_occupancy.saturate": "%", "prompt_slot_share.saturate": "%",
       "step_host_ms.saturate": "ms", "admit_wait_ms.saturate": "ms",
       "reap_wait_ms.saturate": "ms", "prompt_feed_ms.saturate": "ms"}


def _step(t0, t1, active, prompt):
    return Span("engine.step", t0, t1, {"slots": 4, "active": active,
                                        "prompt": prompt,
                                        "generated": active - prompt})


# the window runs from 0 to 10 s; three steps inside it, one after it
EVENTS = {
    "spans": [
        Span("window", 0.0, 10.0, {}),
        Span("live.admit", 0.2, 0.3, {"sandbox": 1, "rid": 0}),
        Span("live.pump", 0.5, 3.6, {"sandbox": 1, "rid": 0, "steps": 3}),
        _step(1.0, 1.5, 2, 1), Span("engine.fetch", 1.2, 1.45, {}),
        _step(2.0, 2.6, 3, 2), Span("engine.fetch", 2.1, 2.5, {}),
        _step(3.0, 3.4, 4, 0), Span("engine.fetch", 3.1, 3.2, {}),
        _step(10.5, 11.0, 4, 4), Span("engine.fetch", 10.6, 10.9, {}),
    ],
    # a decode run with a loop around two ops, an argmax outside it, and a
    # second decode run without the loop
    "ops": [("while", 1.25, 1.4), ("fusion.a", 1.26, 1.3),
            ("copy.b", 1.3, 1.38), ("argmax.1", 1.41, 1.44),
            ("fusion.a", 2.5, 2.55), ("copy.b", 2.55, 2.58)],
    "modules": [("jit_decode_step", 1.25, 1.4), ("jit_argmax", 1.41, 1.44),
                ("jit_decode_step", 2.5, 2.58)],
    "scopes": {"while": "unscoped", "fusion.a": "attn", "copy.b": "unscoped",
               "argmax.1": "unscoped"},
}


def _req(done, stamps, failed=False):
    return NS(done=done, inv=NS(failed=failed, request=NS(**stamps)))


def _stamps(submit, admit, first, last, collected):
    return {"t_submit": submit, "t_admit": admit, "t_first": first,
            "t_last": last, "t_collected": collected}


# waits (admit - submit, first - admit, collected - last): (0.5, 0.5, 2.0),
# (1.5, 0.25, 0.25), (2.5, 0.25, 1.0), and (3.5, 0.5, 4.0) for one done in
# the drain after the window; then one failed, one unfinished and one from
# a program that stamps nothing, all left out. The run is untraced: no
# ``trace`` span, so nothing is taken out of the waits.
REC = NS(seconds=10.0, trace=None, spans={}, steps=[], reqs=[
    _req(5.0, _stamps(100.0, 100.5, 101.0, 101.0, 103.0)),
    _req(8.0, _stamps(200.0, 201.5, 201.75, 202.0, 202.25)),
    _req(9.0, _stamps(300.0, 302.5, 302.75, 303.0, 304.0)),
    _req(12.0, _stamps(400.0, 403.5, 404.0, 404.0, 408.0)),
    _req(6.0, _stamps(0.0, 9.0, 9.0, 9.0, 9.0), failed=True),
    _req(None, _stamps(0.0, 9.0, 9.0, 9.0, 9.0)),
    _req(7.0, {}),
])


def _reader(name):
    return harness._load_module(REPO / "bench" / "metrics" / f"{name}.py",
                                "test_reader_" + name.replace(".", "_")).read


@pytest.fixture
def hand_made(monkeypatch):
    pt = program_trace.reduce_events(EVENTS)
    monkeypatch.setattr(program_trace, "load", lambda tree, rec=None: pt)
    return pt


@pytest.mark.parametrize("metric,want", [
    ("slot_occupancy.saturate", 100.0 * (2 + 3 + 4) / 12),
    ("prompt_slot_share.saturate", 100.0 * (1 + 2 + 0) / 9),
    ("step_host_ms.saturate", 250.0),      # own times 0.25, 0.2, 0.3 s
    ("admit_wait_ms.saturate", 2000.0),    # median of 0.5 to 3.5 s
    ("reap_wait_ms.saturate", 1500.0),     # median of 0.25 to 4.0 s
    ("prompt_feed_ms.saturate", 375.0),    # median of 0.25 to 0.5 s
])
def test_reader_on_hand_made_trace(hand_made, metric, want):
    assert _reader(metric)(REC) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_gives_nothing_for_a_program_without_spans(monkeypatch,
                                                          metric):
    """The benchmark runs these readers on a program that has no spans or
    stamps (the parent of the change that added them): no value, no
    error."""
    pt = program_trace.reduce_events({**EVENTS, "spans": [
        Span("window", 0.0, 10.0, {})]})
    monkeypatch.setattr(program_trace, "load", lambda tree, rec=None: pt)
    bare = NS(seconds=10.0, trace=None, spans={"trace": [(0.0, 4.0)]},
              steps=[harness.Step(30.0, 30.1, [1], 1)],
              reqs=[_req(5.0, {}), NS(done=6.0, inv=NS(failed=False))])
    assert _reader(metric)(bare) is None


def _sub(submitted, done, stamps):
    r = _req(done, stamps)
    r.submitted = submitted
    return r


# A traced run whose window opens at 1000.0 on the stamps' clock: the
# profiler is stopped 10 s in, inside a ``collect``, and the host's next
# step is 30 s later. Request A waits for a slot across the stop, B's last
# token comes before it and its ``collect`` returns after it, C is served
# before it, D after it.
STOPPED = NS(seconds=51.0, trace=None,
             spans={"trace": [(0.0, 10.0)], "collect": [(8.0, 41.0)],
                    "orchestrator": [(0.0, 0.01), (7.9, 41.1)]},
             steps=[harness.Step(9.9, 9.95, [1], 1),
                    harness.Step(40.0, 40.02, [1], 1)],
             reqs=[_sub(0.5, 45.0, _stamps(1000.501, 1041.0, 1041.5, 1042.0,
                                           1042.5)),
                   _sub(0.2, 43.0, _stamps(1000.2, 1000.4, 1001.0, 1009.0,
                                           1041.0)),
                   _sub(0.0, 2.0, _stamps(1000.0002, 1000.3, 1000.8,
                                          1001.0, 1001.5)),
                   _sub(42.0, 50.0, _stamps(1042.0, 1043.0, 1043.5, 1044.0,
                                            1044.25))])


def test_the_profiler_stop_is_where_the_host_next_moved():
    assert program_trace.trace_stop(STOPPED) == (10.0, 40.0)
    assert program_trace.trace_stop(REC) is None
    ended = NS(spans={"trace": [(0.0, 10.0)], "collect": [(1.0, 2.0)]},
               steps=[harness.Step(3.0, 3.1, [1], 1)])
    assert program_trace.trace_stop(ended) is None


def test_the_profiler_stop_is_taken_out_of_the_waits(capsys):
    # A waits 40.499 s to be admitted, 30 of them in the stop; B waits 32 s
    # for its collect, 30 of them in the stop
    assert program_trace.stamp_gaps(STOPPED, "t_submit", "t_admit") == \
        pytest.approx([10.499, 0.2, 0.2998, 1.0])
    assert program_trace.stamp_gaps(STOPPED, "t_last", "t_collected") == \
        pytest.approx([0.5, 2.0, 0.5, 0.25])
    assert program_trace.stamp_gaps(STOPPED, "t_admit", "t_first") == \
        pytest.approx([0.5, 0.6, 0.5, 0.5])
    err = capsys.readouterr().err
    assert err.count("stopping the profiler held the host 30.000000 s") == 1


class _Line:
    """A profile line as ``ProfileData`` gives it: its events are an
    attribute, and the line itself is not iterable."""

    def __init__(self, name, events):
        self.name, self.events = name, events


def _ev(name, t0, t1, stats=()):
    return NS(name=name, start_ns=round(t0 * 1e9),
              duration_ns=round((t1 - t0) * 1e9), stats=list(stats))


def test_events_from_profile_planes():
    planes = [
        NS(name="/host:CPU", lines=[_Line("python", [
            _ev("window", 0.0, 10.0), _ev("orchestrator", 0.5, 4.0),
            _ev("engine.step", 1.0, 1.5, [("slots", 4), ("active", 2)])])]),
        NS(name="/device:TPU:0", lines=[
            _Line("XLA Modules", [_ev("jit_decode_step(7)", 1.25, 1.4)]),
            _Line("XLA Ops", [_ev("while", 1.25, 1.4),
                              _ev("fusion.a", 1.26, 1.3),
                              _ev("fusion.a", 2.5, 2.55)])]),
        NS(name="/device:TPU:1", lines=[
            _Line("XLA Ops", [_ev("other", 0.0, 9.0)])]),
    ]
    ev = program_trace.events(planes, {
        "fusion.a": "jit(decode_step)/while/body/closed_call/mlp/dot:"})
    assert [s.name for s in ev["spans"]] == ["window", "engine.step"]
    assert ev["spans"][1].stats == {"slots": 4, "active": 2}
    assert ev["modules"] == [("jit_decode_step", 1.25, pytest.approx(1.4))]
    assert [o[0] for o in ev["ops"]] == ["while", "fusion.a", "fusion.a"]
    assert ev["scopes"] == {"while": "unscoped", "fusion.a": "mlp"}


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of ``(field, value)``: ints as varints, the rest
    length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_from_the_serialized_trace():
    """The ``tf_op`` stat sits in each op's event metadata, which
    ``ProfileData`` does not give: read it from an ``XSpace`` made by hand
    with ``xplane.proto``'s field numbers."""
    stat_meta = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
                 _msg((1, 8), (2, _msg((1, 8), (2, "flops")))),
                 _msg((1, 9), (2, _msg((1, 9), (2, "jit(f)/while/attn/x"))))]
    ops = [_msg((1, 1), (2, _msg((1, 1), (2, "%fusion.1 = f32[4] fusion()"),
                                 (5, _msg((1, 8), (4, 12))),
                                 (5, _msg((1, 7), (5, "jit(f)/mlp/dot:")))))),
           _msg((1, 2), (2, _msg((1, 2), (2, "%copy.2"),
                                 (5, _msg((1, 7), (7, 9)))))),
           _msg((1, 3), (2, _msg((1, 3), (2, "%tuple.3"))))]
    line = _msg((1, 1), (2, "XLA Ops"), (4, b"\x00" * 40))
    host = _msg((1, 5), (2, "/host:CPU"),
                (4, _msg((1, 1), (2, _msg((2, "engine.step"),
                                          (5, _msg((1, 7), (5, "x"))))))),
                (5, stat_meta[0]))
    device = _msg((1, 6), (2, "/device:TPU:0"), (3, line),
                  *[(4, m) for m in ops], *[(5, m) for m in stat_meta])
    space = _msg((1, host), (1, device), (5, "hostname"))
    assert program_trace.tf_ops(space) == {
        "%fusion.1 = f32[4] fusion()": "jit(f)/mlp/dot:",
        "%copy.2": "jit(f)/while/attn/x"}
    assert program_trace.tf_ops(_msg((1, host))) == {}


def test_spans_are_clipped_to_the_window():
    pt = program_trace.reduce_events(EVENTS)
    assert pt.window == (0.0, 10.0)
    assert [s.t0 for s in pt.named("engine.step")] == [1.0, 2.0, 3.0]
    assert program_trace.reduce_events({**EVENTS, "spans": EVENTS["spans"][1:]}) \
        is None


def test_self_times_take_a_loop_body_out_of_the_loop():
    own = {}
    for name, _, t in program_trace.self_times(EVENTS["ops"]):
        own[name] = own.get(name, 0.0) + t
    assert own == pytest.approx({"while": 0.03, "fusion.a": 0.09,
                                 "copy.b": 0.11, "argmax.1": 0.03})


def test_decode_time_by_scope():
    pt = program_trace.reduce_events(EVENTS)
    dec = program_trace.decode_by_scope(EVENTS, pt)
    assert dec["runs"] == 2
    assert dec["by_scope"] == pytest.approx({"attn": 0.09,
                                             "unscoped": 0.14})
    assert "argmax.1" not in dec["by_op"]
    assert dec["scoped_ops"] == 1


def test_report_warns_when_no_decode_op_has_a_scope(capsys):
    """A compile cache filled by a build without the scopes serves an
    executable whose ops name none: the split is then not the program's."""
    pt = program_trace.reduce_events(EVENTS)
    program_trace.report(EVENTS, pt)
    assert "warning" not in capsys.readouterr().err
    bare = {**EVENTS, "scopes": dict.fromkeys(EVENTS["scopes"], "unscoped")}
    program_trace.report(bare, pt)
    err = capsys.readouterr().err
    assert "warning: no op of jit_decode_step names a scope" in err
    assert "(0 of its 3 ops in a scope)" in err


def test_idle_by_innermost_program_span():
    # busy 1.25-1.4, 1.41-1.44, 2.5-2.58; gaps 0-1.25 and 1.44-2.5 in the
    # pump, 1.4-1.41 in a fetch, 2.58-10 outside the program
    pt = program_trace.reduce_events(EVENTS)
    idle = program_trace.idle_by_span(EVENTS, pt)
    assert idle == pytest.approx({"live.pump": 1.25 + 1.06,
                                  "engine.fetch": 0.01,
                                  "outside_program": 7.42})
    assert sum(idle.values()) == pytest.approx(10.0 - 0.15 - 0.03 - 0.08)


def test_scope_path_from_op_name():
    assert program_trace.scope_of(
        "jit(decode_step)/while/body/closed_call/attn/kv_write/scatter:") \
        == "attn/kv_write"
    assert program_trace.scope_of("jit(decode_step)/logits/dot_general:") \
        == "logits"
    assert program_trace.scope_of("jit(decode_step)/while/dynamic_slice:") \
        == "unscoped"
    assert program_trace.scope_of(None) == "unscoped"


def test_window_must_be_the_reduced_trace_s():
    pt = program_trace.reduce_events(EVENTS)
    program_trace.check_window(pt, None, "t")
    program_trace.check_window(pt, {"window_s": 10.0}, "t")
    with pytest.raises(RuntimeError, match="window"):
        program_trace.check_window(pt, {"window_s": 9.0}, "t")


# -- the traced CPU rehearsal --------------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced rehearsal of ``llm.saturate``, with the harness's record."""
    from bench.tests.tiny import make_tree

    tree = make_tree(tmp_path_factory.mktemp("traced"))
    seen = {}
    close = harness.Harness.close

    def keep(self):
        seen["rec"], seen["slots"] = self.rec, \
            self.cell.config["cluster"]["slots"]
        close(self)

    harness.Harness.close = keep
    try:
        out = harness.run(tree, "llm.saturate", 2**31 + 4321, 2.0, True,
                          time.perf_counter(), rehearsal=True)
    finally:
        harness.Harness.close = close
    return out, seen["rec"], seen["slots"], tree


def test_traced_rehearsal_reports_the_program_metrics(traced):
    out = traced[0]
    assert out["correct"], out["checks"]
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name, unit in NEW.items():
        assert listed[name]["unit"] == unit
        assert "llm.saturate" in listed[name]["workloads"]
        assert out["metrics"][name]["unit"] == unit
        assert out["metrics"][name]["value"] is not None
    assert 0 < out["metrics"]["slot_occupancy.saturate"]["value"] <= 100
    assert out["metrics"]["admit_wait_ms.saturate"]["value"] > 0


def test_program_and_harness_count_the_same_active_slots(traced):
    out, rec, slots, tree = traced
    steps = [s for s in rec.steps
             if any(a <= s.t0 and s.t1 <= b for a, b in rec.spans["trace"])]
    pt = program_trace.load(tree / "bench", rec)
    assert len(pt.named("engine.step")) == len(steps) > 0
    assert out["metrics"]["slot_occupancy.saturate"]["value"] == pytest.approx(
        100.0 * sum(len(s.lens) for s in steps) / (slots * len(steps)),
        rel=1e-12)
