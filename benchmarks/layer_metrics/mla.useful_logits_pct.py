"""Layer `mla`: how much of what the latent attention kernel computes
it keeps: 100 x sum `mla_logits_useful` / sum `mla_logits_issued` over
the window's flight records, both summed over the latent layers.
`useful` is the causal (query, key) pairs x the query heads; `issued`
is the logits the kernel's products give for the same runs by its own
tiles (`mla_attention.mla_work`: a q tile of its height x the heads
meets every fetched group of keys that does not lie past its last
query). The rest is masked away: keys past a query in its tile's
groups, a tile's overhang past its run. DERIVED from the plan by the
kernel's rule, not measured. None where the program records no such
fields."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("mla_logits_issued")]
    if not recs:
        return None
    useful = sum(r["mla_logits_useful"] for r in recs)
    issued = sum(r["mla_logits_issued"] for r in recs)
    ctx.log(f"mla_attend logits: {issued / len(recs):.4g} issued a step "
            f"for {useful / len(recs):.4g} useful, over {len(recs)} "
            f"steps")
    return 100.0 * useful / issued
