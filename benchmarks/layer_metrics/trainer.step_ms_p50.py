"""Layer `trainer`: median host-clock time of one train step, each
ended by `block_until_ready` (the traced run's blocking steps; the
measured window of the untraced run never blocks on the newest step)."""
from harness.stats import percentile


def read(ctx):
    return percentile([s * 1e3 for s in ctx.steps or []], 50)
