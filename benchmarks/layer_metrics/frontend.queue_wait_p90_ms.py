"""Layer `frontend`: 90th percentile of the wait between a request's
`enqueued` and `admitted` span events (`Trace.derive()["queue_wait"]`),
over the requests enqueued inside the window."""
from harness.stats import percentile


def read(ctx):
    waits = [s["queue_wait"] * 1e3 for s in ctx.spans
             if s.get("queue_wait") is not None]
    return percentile(waits, 90)
