"""The host's own time per batcher step: the median, over the traced
window's ``engine.step`` spans, of the step's length less that of its
``engine.fetch`` child, the blocking wait for the argmax. The device has no
next step to run while the host does this work."""
import bisect
import statistics
from pathlib import Path

from bench import program_trace

TREE = Path(__file__).resolve().parents[1]


def read(rec):
    pt = program_trace.load(TREE, rec)
    if pt is None:
        return None
    fetches = pt.named("engine.fetch")
    starts = [f.t0 for f in fetches]
    own = []
    for s in pt.named("engine.step"):
        i = bisect.bisect_left(starts, s.t0)
        if i < len(fetches) and fetches[i].t1 <= s.t1:
            own.append((s.t1 - s.t0) - (fetches[i].t1 - fetches[i].t0))
    return 1e3 * statistics.median(own) if own else None
