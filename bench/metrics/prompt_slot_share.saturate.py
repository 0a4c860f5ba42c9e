"""Share of the active slots of the ``engine.step`` spans in the traced part
of the window (its first 10 s) that fed a prompt token and generated
nothing: the sum of their ``prompt`` stats over the sum of their ``active``
stats. Prompts go in one token a step, so this is the share of the batch
spent on them."""
from pathlib import Path

from bench import program_trace

TREE = Path(__file__).resolve().parents[1]


def read(rec):
    pt = program_trace.load(TREE, rec)
    steps = pt.named("engine.step") if pt else []
    active = sum(s.stats["active"] for s in steps)
    if not active:
        return None
    return 100.0 * sum(s.stats["prompt"] for s in steps) / active
