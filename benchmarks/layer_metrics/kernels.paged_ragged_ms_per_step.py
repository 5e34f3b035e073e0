"""Layer `kernels`: device time of the `paged_ragged` Mosaic kernel per
engine step, from the profiled slice: the summed duration of the
device events whose name contains `paged_ragged`, over the executions
of the mixed-step program in the same slice."""

KERNEL = "paged_ragged"
PROGRAM = "serving_mixed_step"


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    if not seconds or not steps:
        return None
    ctx.log(f"paged_ragged: {seconds:.4f} s in "
            f"{ctx.trace.calls_of(KERNEL):.0f} kernel calls over "
            f"{steps:.0f} mixed steps of the slice")
    return seconds * 1e3 / steps
