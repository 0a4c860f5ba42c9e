"""Fixtures for the benchmark's CPU rehearsals."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (REPO / "src", REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def tree(tmp_path):
    """A tiny-size copy of the benchmark (``bench.tests.tiny``)."""
    from bench.tests.tiny import make_tree

    return make_tree(tmp_path)


@pytest.fixture
def rehearse(tree):
    """Run a cell of the tiny tree on the CPU through the harness's
    internal entry; returns the result line's object."""
    import time

    from bench.harness import run

    def go(workload, seed=2**31 + 12345, seconds=2.0, trace=False, **kw):
        return run(tree, workload, seed, seconds, trace, time.perf_counter(),
                   rehearsal=True, **kw)
    return go
