"""Layer `mixed_step`: what the tracer itself costs a step: p50 over
the window of the flight field `trace_self`, the seconds of a step's
SUMMED host phases (plan, pack, dispatch, emit, note: what
`mixed_step.host_ms_per_step` adds up; the wait for the device left
out) that the engine spent in tracing code, on its own clock: the span
events, the phase marks, what the flight record reads. The untraced
runs the driver judges pay none of it. Logs
`mixed_step.host_ms_per_step` less it, the host's own work a step. None
where the program does not record the field (before PR 35)."""
from harness.stats import percentile

HOST = ("ph_plan", "ph_pack", "ph_dispatch", "ph_emit", "ph_note")


def read(ctx):
    recs = [r for r in ctx.flight if "trace_self" in r]
    if not recs:
        return None
    own = percentile([r["trace_self"] * 1e3 for r in recs], 50)
    host = percentile([sum(r.get(f, 0.0) for f in HOST) * 1e3
                       for r in recs], 50)
    ctx.log(f"tracing code in the summed phases ms p50 {own:.3f} of "
            f"{host:.3f} (host_ms_per_step): {host - own:.3f} the "
            f"host's own work; p90 "
            f"{percentile([r['trace_self'] * 1e3 for r in recs], 90):.3f}"
            f" over {len(recs)} steps")
    return own
