"""Layer `kernels`: device time of the gated delta rule (`gated_delta`,
the Mosaic kernel that carries each slot's recurrent state through its
run: a call a linear layer) per engine step, from the profiled slice:
the summed duration of the device events whose name contains
`gated_delta` over the executions of the mixed-step program in the same
slice. None where no such event ran (a CPU rehearsal, or a program
without it)."""

KERNEL = "gated_delta"
PROGRAM = "serving_mixed_step"


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    if not seconds or not steps:
        return None
    ctx.log(f"gated_delta: {seconds:.4f} s in "
            f"{ctx.trace.calls_of(KERNEL):.0f} kernel calls over "
            f"{steps:.0f} mixed steps of the slice")
    return seconds * 1e3 / steps
