"""Layer `linear_attn`: what share of the rows of q, k, v the delta-rule
kernel loads are real tokens, over the steps of the window: 100 x sum
of `lin_tokens` / sum of `lin_rows_walked` (flight record, one linear
layer; every linear layer walks the same runs). `lin_rows_walked` is
the program's own count of what its kernel loads for the step's runs: a
run of one token its one row, a chunk of more its tile, a chunk slot
that holds nothing no row. None where the program does not record the
field."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("lin_rows_walked")]
    if not recs:
        return None
    tokens = sum(r["lin_tokens"] for r in recs)
    rows = sum(r["lin_rows_walked"] for r in recs)
    n = len(recs)
    ctx.log(f"linear layers: the kernel loads {rows / n:.1f} rows a "
            f"step for {tokens / n:.1f} tokens; "
            f"{sum(r.get('lin_single_runs', 0) for r in recs) / n:.1f} "
            f"of {sum(r['lin_runs'] for r in recs) / n:.1f} runs hold "
            f"one token ({n} steps)")
    return 100.0 * tokens / rows
