"""Share of the HBM roofline that the served decode step reaches: the bytes
the step needs (the payload's ``step_bytes``, from each step's active cache
lengths) over the device time of the decode executable in the trace,
against the chip's peak bandwidth. The FLOP bound is far lower at these shapes, and
the larger of the two bounds is the one taken."""

MODULE = "jit_decode_step"


def read(rec):
    if rec.trace is None:
        return None
    mod = rec.trace["modules"].get(MODULE)
    if mod is None:
        # a device trace without the served step: the executable was
        # renamed, and the metric must not go silent
        raise RuntimeError(f"no {MODULE} in the trace's executables: "
                           f"{sorted(rec.trace['modules'])}")
    steps = rec.traced_steps()
    if not mod or not mod[1] or not steps or rec.peaks is None:
        return None
    n, device_s = mod
    dtype = rec.arch.get("dtype", "bfloat16")
    need_s = 0.0
    for s in steps:
        need_s += max(rec.payload.step_bytes(rec.arch, dtype, s.lens)
                      / rec.peaks["hbm_bytes_per_s"],
                      rec.payload.step_flops(rec.arch, s.lens)
                      / rec.peaks["bf16_flops_per_s"])
    # the trace's count of decode runs and the harness's count of steps
    # cover the same window; scale by the trace's count
    return 100.0 * (need_s / len(steps)) * n / device_s
