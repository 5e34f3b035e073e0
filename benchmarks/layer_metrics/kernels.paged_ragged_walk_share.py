"""Layer `kernels`: how much of the KV the paged attention kernel
fetches it has to fetch: 100 x sum `kv_blocks_needed` / sum
`kv_blocks_walked` over the window's flight records. `needed` is one
read of every fed slot's context, in blocks; `walked` is the (query
group, KV block) tiles the kernel's walks visit for the same plan, by
the kernel's own grouping rule. 100% when every context is walked once
a step; a kernel that walks once per query token reads about a quarter.
None where the program does not record them."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("kv_blocks_walked")]
    if not recs:
        return None
    needed = sum(r["kv_blocks_needed"] for r in recs)
    walked = sum(r["kv_blocks_walked"] for r in recs)
    ctx.log(f"paged_ragged walks: {walked / len(recs):.1f} KV blocks "
            f"fetched a step for {needed / len(recs):.1f} needed, over "
            f"{len(recs)} steps")
    return 100.0 * needed / walked
