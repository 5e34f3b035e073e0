"""The flight records of the profiled slice. A reader that holds a
kernel's device time (from the slice) against the program's counts has
to count the SAME steps: where the driver hands the slice's bounds on
the host's monotonic clock (`ctx.slice = (start, stop)`, the clock the
flight record's `ts` is on), the records that began inside them;
otherwise every record of the window, whose mean step then stands for
the slice's (fair in a closed loop of short requests, not where a
window holds phases of long prompts)."""
from __future__ import annotations


def records(ctx, field):
    """Flight records that carry `field`, of the slice if its bounds
    are known and hold any, else of the window."""
    recs = [r for r in ctx.flight if field in r]
    bounds = getattr(ctx, "slice", None)
    if bounds and bounds[0] is not None and bounds[1] is not None:
        inside = [r for r in recs if bounds[0] <= r["ts"] < bounds[1]]
        if inside:
            return inside, "slice"
    return recs, "window"
