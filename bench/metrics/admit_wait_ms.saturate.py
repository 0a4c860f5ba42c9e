"""Median wait of a request between its submission at the front end
(``LiveRequest.t_submit``, core/cluster.py) and its admission into a batcher
slot (``t_admit``, live/backend.py), over the window's requests that
finished: the data plane's queue and the hops, held back behind the serial
pump. The host's hold while the profiler stops is taken out
(``program_trace.stamp_gaps``)."""
import statistics

from bench import program_trace


def read(rec):
    waits = program_trace.stamp_gaps(rec, "t_submit", "t_admit")
    return 1e3 * statistics.median(waits) if waits else None
