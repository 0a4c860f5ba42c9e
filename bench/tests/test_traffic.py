"""The generators are deterministic, every run seed gets the same schedule
with its own token ids, and the wall-clock pacing counts a stall against
the requests due behind it."""
import json
from collections import Counter

import numpy as np
import pytest

from bench.harness import _load_module
from bench.tests.conftest import REPO

FUNCTIONS = ["llm", "hot-0", "hot-1"] + [f"timer-{i:02d}" for i in range(10)]
MIXES = sorted(p.stem for p in (REPO / "bench" / "traffic").glob("*.json"))


def _source(mix, schedule_seed=None, seconds=30.0):
    t = json.loads((REPO / "bench" / "traffic" / f"{mix}.json").read_text())
    kind = _load_module(REPO / "bench" / "traffic" / "kinds"
                        / f"{t['kind']}.py", "kind_" + t["kind"])
    seed = t["schedule_seed"] if schedule_seed is None else schedule_seed
    return kind.make(t["params"], FUNCTIONS, np.random.default_rng(seed),
                     seconds)


def _drain(src, seconds=30.0):
    """Everything the source sends, completing each request 1 s after it
    was sent (closed loops send again on completion)."""
    out, pending = [], []
    t = 0.0
    while t < seconds:
        for a in src.pop_due(t):
            out.append(a)
            pending.append((t + 1.0, a))
        pending.sort(key=lambda p: p[0])
        while pending and pending[0][0] <= t:
            src.on_done(pending.pop(0)[1], t)
        t += 0.05
    out += src.pop_due(seconds)
    return [(round(a.due, 9), a.function, a.prompt_len, a.max_new)
            for a in out]


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_is_deterministic(mix):
    assert _drain(_source(mix)) == _drain(_source(mix))


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_seed_reorders_the_same_work(mix):
    a, b = _drain(_source(mix)), _drain(_source(mix, 2**31 + 99))
    assert a != b
    assert len(a) == len(b)
    if mix != "closed_48":     # a closed loop's count follows completions
        assert Counter(x[2] for x in a) == Counter(x[2] for x in b)
        assert Counter(x[3] for x in a) == Counter(x[3] for x in b)
    assert all(0.0 <= x[0] < 30.0 for x in a)


def test_run_seed_draws_only_the_token_ids(tree, rehearse, monkeypatch):
    """Two ``--seed`` values: the same schedule, other prompts."""
    import bench.harness as H
    from bench.tests.tiny import add_cell

    seen = []
    real = H.Harness.window

    def window(self, seconds):
        out = real(self, seconds)
        seen.append([(round(r.due, 9), r.arrival.function,
                      r.arrival.prompt_len, r.arrival.max_new,
                      tuple(r.inv.request.prompt)) for r in self.rec.reqs])
        return out
    monkeypatch.setattr(H.Harness, "window", window)
    add_cell(tree, "faas.burst")
    rehearse("faas.burst", seed=5)
    rehearse("faas.burst", seed=2**31 + 7)
    a, b = seen
    assert [x[:4] for x in a] == [x[:4] for x in b]
    assert [x[4] for x in a] != [x[4] for x in b]


def test_poisson_rate_and_span():
    src = _source("poisson_steady", seconds=40.0)
    dues = [a.due for a in src.pop_due(1e9)]
    rate = json.loads((REPO / "bench" / "traffic" / "poisson_steady.json")
                      .read_text())["params"]["rate"]
    assert len(dues) == round(rate * 40.0)
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 40.0


def test_stall_counts_against_requests_due_behind_it(tree, rehearse,
                                                     monkeypatch):
    """A payload call that blocks the host for STALL seconds: requests due
    meanwhile are submitted late, and their latency runs from when they
    were due, so each carries at least the rest of the stall."""
    import time

    from repro.live.backend import LiveBackend

    STALL = 0.6
    real = LiveBackend.collect
    stalled, seen = {}, {}

    def collect(self, ticket):
        if seen.get("armed") and not stalled:
            stalled["t"] = time.perf_counter()
            time.sleep(STALL)
            stalled["end"] = time.perf_counter()
        return real(self, ticket)

    monkeypatch.setattr(LiveBackend, "collect", collect)
    import bench.harness as H

    real_window = H.Harness.window

    def window(self, seconds):
        seen["armed"] = True
        out = real_window(self, seconds)
        seen["rec"], seen["t0"] = self.rec, self.t0
        return out

    monkeypatch.setattr(H.Harness, "window", window)
    from bench.tests.tiny import add_cell
    out = rehearse(add_cell(tree, "llm.steady"), seconds=2.0)
    assert out["correct"]
    rec, t0 = seen["rec"], seen["t0"]
    s0, s1 = stalled["t"] - t0, stalled["end"] - t0
    behind = [r for r in rec.reqs if s0 < r.due < s1]
    assert behind, "no request fell due during the stall"
    for r in behind:
        assert r.submitted >= s1 - 1e-3
        assert r.done - r.due >= s1 - r.due


def test_arrivals_due_while_the_host_is_blocked_past_the_close_are_sent(
        tree, rehearse, monkeypatch):
    """The host blocked twice in calls that bill no virtual time: the first
    leaves the DES behind the wall clock, so the next advance both serves
    everything sent so far and, blocked again, runs past the close. Every
    arrival due in the window is still sent and served."""
    import time

    import bench.harness as H
    from bench.tests.tiny import add_cell
    from repro.live.backend import LiveBackend

    real = LiveBackend.admit
    stalls, seen = [1.2, 1.5], {}

    def admit(self, sandbox_id, req):
        if seen.get("armed") and stalls:
            time.sleep(stalls.pop(0))
        return real(self, sandbox_id, req)

    monkeypatch.setattr(LiveBackend, "admit", admit)
    real_window = H.Harness.window

    def window(self, seconds):
        seen["armed"] = True
        out = real_window(self, seconds)
        seen["n"] = len(self.rec.reqs)
        return out
    monkeypatch.setattr(H.Harness, "window", window)
    out = rehearse(add_cell(tree, "llm.steady"), seconds=2.0)
    assert not stalls and out["correct"], out["checks"]
    t = json.loads((tree / "bench" / "traffic" / "poisson_steady.json")
                   .read_text())
    assert seen["n"] == out["attempted"] == round(t["params"]["rate"] * 2.0)
