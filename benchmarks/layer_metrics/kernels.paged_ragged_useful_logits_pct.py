"""Layer `kernels`: how much of what the paged attention kernel computes
it keeps: 100 x sum `attn_logits_useful` / sum `attn_logits_issued` over
the window's flight records, both summed over the step's attention
layers. `useful` is the (query, key) pairs x the model's query heads;
`issued` is the logits the kernel's products give (and its softmax
exponentiates) for the same plan, by the kernel's own tiles: products x
rows x columns, the tiles its causal and window rules skip left out. The
rest is masked away: other heads' columns, keys past a query, a tile's
overhang. None where the program does not record them."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("attn_logits_issued")]
    if not recs:
        return None
    useful = sum(r["attn_logits_useful"] for r in recs)
    issued = sum(r["attn_logits_issued"] for r in recs)
    ctx.log(f"paged_ragged logits: {issued / len(recs):.3g} issued a "
            f"step for {useful / len(recs):.3g} useful, over "
            f"{len(recs)} steps")
    return 100.0 * useful / issued
