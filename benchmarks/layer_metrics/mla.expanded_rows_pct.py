"""Layer `mla`: how many of the latent attention's chunk rows take the
EXPANDED walk (`mla_expand_attend`: a slot's latent context
up-projected into per-head keys and values once a step, for every row
of its chunk): 100 x sum `mla_rows_expanded` / sum `mla_rows_chunk`
over the window's flight records, both summed over the latent layers.
The program chooses the form by block of positions from the FLOPs of
the two (`mla_attention.ExpandRule`); the chunk rows left are blocks
too short to repay the up-projection, which stay on `mla_attend`. DERIVED from the
plan by the program's rule, not measured. None where no record has the
field (a program without the expanded walk), the window fed no chunk
row, or no row took the walk (a step whose budget holds no block, as in
the CPU rehearsal's)."""


def read(ctx):
    recs = [r for r in ctx.flight if "mla_rows_expanded" in r]
    chunk = sum(r["mla_rows_chunk"] for r in recs)
    expanded = sum(r["mla_rows_expanded"] for r in recs)
    if not expanded:
        return None
    ctx.log(f"mla expanded walk: {expanded / len(recs):.1f} of "
            f"{chunk / len(recs):.1f} chunk rows a step over the latent "
            f"layers ({len(recs)} steps)")
    return 100.0 * expanded / chunk
