"""Share of the batcher's slots that held a request, over the ``engine.step``
spans of the traced part of the window (its first 10 s, where the first
wave fills the most slots): the sum of their ``active`` stats over the sum
of their ``slots`` (serving/engine.py)."""
from pathlib import Path

from bench import program_trace

TREE = Path(__file__).resolve().parents[1]


def read(rec):
    pt = program_trace.load(TREE, rec)
    steps = pt.named("engine.step") if pt else []
    if not steps:
        return None
    return (100.0 * sum(s.stats["active"] for s in steps)
            / sum(s.stats["slots"] for s in steps))
