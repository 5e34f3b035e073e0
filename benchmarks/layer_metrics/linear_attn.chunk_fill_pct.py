"""Layer `linear_attn`: what share of the chunk rows the delta-rule
kernel walks are real tokens, over the steps of the window: 100 x sum
of `lin_tokens` / sum of (`lin_chunks` x `lin_chunk_size`) (flight
record, one linear layer; every linear layer walks the same runs). A
prefill chunk fills its rows; a decode run fills 1 row of its chunk.
None where the program does not record them."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("lin_chunks")]
    if not recs:
        return None
    tokens = sum(r["lin_tokens"] for r in recs)
    rows = sum(r["lin_chunks"] * r["lin_chunk_size"] for r in recs)
    n = len(recs)
    ctx.log(f"linear layers: a mean step of the window feeds "
            f"{tokens / n:.1f} tokens in "
            f"{sum(r['lin_runs'] for r in recs) / n:.1f} runs "
            f"({sum(r['lin_chunks'] for r in recs) / n:.1f} chunks of "
            f"{recs[-1]['lin_chunk_size']} rows) to a linear layer; "
            f"{sum(r.get('state_slots_in_use', 0) for r in recs) / n:.1f}"
            f" slots hold a live state ({n} steps)")
    return 100.0 * tokens / rows
