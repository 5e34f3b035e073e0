"""Layer `mla`: device time a mixed step of the latent attention, the
scopes `attn_latent` (the `mla_attend` kernel and what surrounds it:
the run tables' use, the zeroing of padding rows) and `mla_absorb` (the
products with W_UK and W_UV), by the live engine's own table of
instruction -> scope (`harness/device_scopes.py`), over the mixed steps
of the profiled slice. Logs the kernel's own part (the device events
named `mla_attend`) and the split of the window's causal pairs between
decode and chunk rows. None where the step sets neither scope."""
from harness import device_scopes

SCOPES = ("attn_latent", "mla_absorb")
KERNEL, PROGRAM = "mla_attend", "serving_mixed_step"


def read(ctx):
    total = device_scopes.ms_per_step(ctx, *SCOPES)
    if total is None:
        return None
    found = device_scopes.of(ctx)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    kernel = ctx.trace.seconds_of(KERNEL) * 1e3 / steps if steps else 0.0
    recs = [r for r in ctx.flight if "mla_pairs_chunk" in r]
    dec = sum(r["mla_pairs_decode"] for r in recs)
    chunk = sum(r["mla_pairs_chunk"] for r in recs)
    ctx.log(f"latent attention, ms a step: {', '.join(f'{s} {found.ms(s):.3f}' for s in SCOPES)}"
            f"; the kernel {kernel:.3f} of it; of the window's causal "
            f"pairs {100.0 * dec / max(dec + chunk, 1):.2f}% are decode "
            f"rows' ({len(recs)} steps)")
    return total
