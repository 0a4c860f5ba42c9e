"""Model FLOP utilization of the whole serving path: the FLOPs of every
token fed through a decode step in the window (prompt and generated, each
attending to its context), over the window times the chip's bf16 peak."""
from bench import flops


def read(rec):
    steps = rec.window_steps()
    if not steps or rec.peaks is None:
        return None
    total = sum(flops.step_flops(rec.arch, s.lens) for s in steps)
    return 100.0 * total / (rec.seconds * rec.peaks["bf16_flops_per_s"])
