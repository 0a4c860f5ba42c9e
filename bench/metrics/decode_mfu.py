"""Model FLOP utilization of the whole serving path: the FLOPs of every
token fed through a decode step in the traced part of the window (prompt and
generated, each attending to its context), over that part's length times the
chip's bf16 peak. Stopping the profiler holds the host with no step running,
so the rest of the window is left out of both."""


def read(rec):
    steps = rec.traced_steps()
    if not steps or rec.peaks is None:
        return None
    total = sum(rec.payload.step_flops(rec.arch, s.lens) for s in steps)
    return 100.0 * total / (rec.traced_seconds()
                            * rec.peaks["bf16_flops_per_s"])
