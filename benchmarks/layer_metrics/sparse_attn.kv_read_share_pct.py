"""Layer `sparse_attn`: what the decode rows of a model that attends
through a learned selection read of what dense attention would: 100 x
sum `sparse_kv_tokens_read` / sum `sparse_kv_tokens_context` over the
window's flight records (one layer each; every sparse layer reads
alike). A decode row at context c reads min(topk, c) K/V tokens, so the
share falls as the contexts grow past `topk`. DERIVED from the step's
plan, not measured: both fields are host arithmetic (`engine.
_sparse_work`), so the share is a constant of the traffic and of `topk`
which no change to the program moves, and it does not see what the
score path reads (`idx_score` gathers a decode row's whole table of
indexer pages whatever its context). It says what mix the window held;
the bytes `idx_score` and `attn_sparse` read over their device time are
the number a `perf_opt` should track (`sparse_attn.indexer_ms_per_step`,
`kernels.sparse_attend_roofline`). None where the program records no
such fields (a program without sparse layers, or before
PR 37) or no decode row ran."""


def read(ctx):
    recs = [r for r in ctx.flight if "sparse_kv_tokens_context" in r]
    context = sum(r["sparse_kv_tokens_context"] for r in recs)
    if not context:
        return None
    read_ = sum(r["sparse_kv_tokens_read"] for r in recs)
    rows = sum(r["sparse_rows_decode"] for r in recs)
    kept = sum(r["sparse_pairs_kept"] for r in recs)
    causal = sum(r["sparse_pairs_causal"] for r in recs)
    ctx.log(f"sparse attention: over {len(recs)} steps "
            f"{rows / len(recs):.1f} decode rows a step at a mean "
            f"context of {context / max(rows, 1):.0f} read "
            f"{read_ / max(rows, 1):.0f} K/V tokens each, a layer; the "
            f"chunk rows' selections keep "
            f"{100.0 * kept / max(causal, 1):.1f}% of their causal pairs"
            f" ({sum(r['sparse_rows_chunk'] for r in recs) / len(recs):.0f}"
            f" rows a step); {sum(r['idx_keys_scored'] for r in recs) / len(recs):.3g}"
            f" indexer scores a step a layer; indexer-key pools "
            f"{recs[-1].get('idx_pool_bytes', 0) / 1e9:.3f} GB")
    return 100.0 * read_ / context
