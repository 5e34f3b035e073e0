"""Layer `device`: share of the profiled slice in which no operation
ran on the chip (1 - union of op intervals / slice), training cells."""


def read(ctx):
    if not ctx.trace.window_s:
        return None
    return 100.0 * ctx.trace.idle_share
