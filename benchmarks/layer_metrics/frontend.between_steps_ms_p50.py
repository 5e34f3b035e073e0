"""Layer `frontend`: median time between the end of one `engine.step()`
and the start of the next (flight record `gap_before`, the engine's
clock): the hop out of the executor, `_stream_blocks` and `_publish`,
cancellations and admission, the hop back in. Logs the four frontend
phases' medians (`ph_hop_out`, `ph_publish`, `ph_admit`, `ph_hop_in`,
which ride on the record of the step they precede). None where the
program records no gap."""
from harness.stats import percentile

PHASES = ("ph_hop_out", "ph_publish", "ph_admit", "ph_hop_in")


def read(ctx):
    gaps = [r["gap_before"] * 1e3 for r in ctx.flight
            if "gap_before" in r]
    if not gaps:
        return None
    p50 = {f: percentile([r[f] * 1e3 for r in ctx.flight if f in r], 50)
           for f in PHASES}
    ctx.log(f"frontend phases ms p50 over {len(gaps)} gaps (longest gap "
            f"{max(gaps):.2f} ms): " + " ".join(
                f"{f[3:]} {v:.3f}" for f, v in p50.items()
                if v is not None))
    return percentile(gaps, 50)
