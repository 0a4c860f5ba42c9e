"""Median wait of a finished request for its ``collect`` to return: from
its last output token (``LiveRequest.t_last``, serving/engine.py) to the
return of ``collect`` (``t_collected``, live/backend.py), over the window's
requests that finished. A request that finished while another request's
pump held the host waits here. The host's hold while the profiler stops is
taken out (``program_trace.stamp_gaps``)."""
import statistics

from bench import program_trace


def read(rec):
    waits = program_trace.stamp_gaps(rec, "t_last", "t_collected")
    return 1e3 * statistics.median(waits) if waits else None
