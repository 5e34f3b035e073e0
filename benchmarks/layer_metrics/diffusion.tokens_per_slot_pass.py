"""Layer `diffusion`: tokens DECIDED a slot pass, over the steps of the
window: sum of `diff_tokens_decided` / sum of `diff_slot_passes`
(flight record of a model that decodes by blocks; a slot pass is one
slot feeding its block's L rows through one step). A block of L costs
its denoise passes and one commit, which decides nothing: at one
position a pass, L / (L + 1). Logs the commits' share of the slot
passes and the masked rows a denoise pass. None where the program does
not record them."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("diff_slot_passes")]
    if not recs:
        return None
    passes = sum(r["diff_slot_passes"] for r in recs)
    decided = sum(r["diff_tokens_decided"] for r in recs)
    commits = sum(r["diff_commits"] for r in recs)
    masked = sum(r["diff_rows_masked"] for r in recs)
    n = len(recs)
    ctx.log(f"diffusion: a mean step of the window holds {passes / n:.1f} "
            f"slot passes of {recs[-1]['diff_block_len']} rows, of them "
            f"{commits / n:.1f} commits ({commits / passes:.1%}; "
            f"{sum(r['diff_blocks_committed'] for r in recs) / n:.1f} "
            f"blocks committed a step); a denoise pass feeds "
            f"{masked / max(passes - commits, 1):.2f} masked rows and "
            f"decides {decided / max(passes - commits, 1):.2f} ({n} steps)")
    return decided / passes
