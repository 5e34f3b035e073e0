"""Layer `mixed_step`: what share of the window's mixed steps were
dispatched while the step before them was still unread: 100 x the
records with `ahead == 1` / the records that carry the field (flight
record; `ahead` is 1 where `engine.step()` planned, packed and launched
its step before it read the last one back, so the host's work lay
behind the device, and 0 where the engine runs in the synchronous order:
a drain before a preemption, the first step after an idle point, an
engine whose next plan needs the tokens on the host). Logs
`ahead_wasted_rows` a step beside it: rows fed to a request that had
already ended on EOS, one step before the host learned it. None where
the program does not record the field."""


def read(ctx):
    recs = [r for r in ctx.flight if "ahead" in r]
    if not recs:
        return None
    ahead = sum(r["ahead"] == 1 for r in recs)
    wasted = sum(r.get("ahead_wasted_rows", 0) for r in recs)
    ctx.log(f"dispatched ahead: {ahead} of {len(recs)} steps; "
            f"{wasted / len(recs):.3f} rows a step fed to a request that "
            f"had ended ({wasted} in the window)")
    return 100.0 * ahead / len(recs)
