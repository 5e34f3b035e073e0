"""Layer `mixed_step`: median wall time of one `engine.step()` in the
window — schedule, pack, dispatch, device, readback, emit (flight
recorder `dur`)."""
from harness.stats import percentile


def read(ctx):
    durs = [r["dur"] * 1e3 for r in ctx.flight if "dur" in r]
    return percentile(durs, 50)
