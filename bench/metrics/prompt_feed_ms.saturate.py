"""Median time from a request's admission into a batcher slot
(``LiveRequest.t_admit``, live/backend.py) to its first output token
(``t_first``, serving/engine.py), over the window's requests that finished:
its prompt fed one token a step, on steps that run only while some request's
pump holds the host. The host's hold while the profiler stops is taken out
(``program_trace.stamp_gaps``)."""
import statistics

from bench import program_trace


def read(rec):
    feeds = program_trace.stamp_gaps(rec, "t_admit", "t_first")
    return 1e3 * statistics.median(feeds) if feeds else None
