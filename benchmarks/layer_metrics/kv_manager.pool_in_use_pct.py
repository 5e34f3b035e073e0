"""Layer `kv_manager`: share of the KV pool's blocks that hold a
request's tokens, averaged over the steps of the window: 100 x mean of
`kv_blocks_in_use / kv_blocks_total`, read by the engine at the end of
each step (flight record). A pool the traffic does not fill is padding.
Logs the peak and the preemptions since the window's first record.
None where the program does not record them."""


def read(ctx):
    recs = [r for r in ctx.flight if r.get("kv_blocks_total")]
    if not recs:
        return None
    used = [r["kv_blocks_in_use"] for r in recs]
    total = recs[-1]["kv_blocks_total"]
    ctx.log(f"kv pool by the program's count: {total} blocks; in use "
            f"mean {sum(used) / len(used):.0f}, peak {max(used)} "
            f"({max(used) / total:.1%}), least {min(used)} over "
            f"{len(recs)} steps; "
            f"{recs[-1]['preemptions'] - recs[0]['preemptions']} "
            "preemptions since the window's first step")
    return 100.0 * sum(r["kv_blocks_in_use"] / r["kv_blocks_total"]
                       for r in recs) / len(recs)
