"""Layer `kv_manager`: what the window allocator holds against what it
would hold with no window: 100 x tokens of the slots' contexts that
still lie in window-layer blocks / tokens of the same contexts, summed
over the steps of the window (flight record `kv_tokens_held_window`,
`kv_tokens_context`, read at the end of each step). 100 means nothing
was released. Logs the blocks in use of each kind and the blocks
released behind the window. None where the program records none."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("kv_tokens_context")]
    if not recs:
        return None
    held = sum(r["kv_tokens_held_window"] for r in recs)
    context = sum(r["kv_tokens_context"] for r in recs)
    n = len(recs)
    ctx.log(f"kv by kind: window-layer blocks in use mean "
            f"{sum(r['kv_blocks_in_use_window'] for r in recs) / n:.0f} "
            f"peak {max(r['kv_blocks_in_use_window'] for r in recs)}; "
            f"full-layer blocks mean "
            f"{sum(r['kv_blocks_in_use_full'] for r in recs) / n:.0f} "
            f"peak {max(r['kv_blocks_in_use_full'] for r in recs)}; "
            f"{sum(r['kv_blocks_released_behind_window'] for r in recs)} "
            f"blocks released behind the window over {n} steps")
    return 100.0 * held / context
