"""Layer `sparse_attn`: what share of the selection's table the exact
top-k walks, over the window's flight records (one layer each; every
sparse layer walks alike): 100 x sum `idx_cols_walked` / sum
`idx_cols_width`. `idx_cols_walked` is the program's own count, by its
kernel's rule (`topk_select.cols_walked`), of the columns its counting
passes walk for the step's chunk and decode rows: a tile of rows walks
its largest candidate column + 1, rounded up to the kernel's strip;
`idx_cols_width` is the same rows x the table's width, what a pass over
every column walks. DERIVED from the plan, not measured: it says how
much work the kernel skips, and its device time is read from the trace
(`sparse_attn.indexer_ms_per_step`, scope `idx_select`). None where the
program records no such field."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("idx_cols_width")]
    if not recs:
        return None
    walked = sum(r["idx_cols_walked"] for r in recs)
    width = sum(r["idx_cols_width"] for r in recs)
    ctx.log(f"exact top-k: {walked / len(recs):.4g} of "
            f"{width / len(recs):.4g} columns a step a layer walked "
            f"({len(recs)} steps)")
    return 100.0 * walked / width
