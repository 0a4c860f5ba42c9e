"""Median wall time of one ``ContinuousBatcher.step`` in the window, from the
harness's wrapper around it; each step ends blocked on the argmax."""
import statistics


def read(rec):
    walls = [s.t1 - s.t0 for s in rec.window_steps()]
    return 1e3 * statistics.median(walls) if walls else None
