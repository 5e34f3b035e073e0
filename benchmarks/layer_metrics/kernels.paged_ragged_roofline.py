"""Layer `kernels`: roofline share of the `paged_ragged` Mosaic kernel.
The least time the chip needs for the attention of the mixed steps of
the profiled slice — the larger of FLOPs / peak FLOP/s and bytes / peak
B/s, `harness/paged_attention.py` fed the program's token counts —
over the device time of the events whose name contains `paged_ragged`.

The work is that of the WINDOW's mean step (flight records
`kv_tokens_read`, `attn_pairs`, `prefill_tokens` + `decode_tokens`)
times the executions of the mixed-step program in the slice: the
records of the slice itself cannot be picked out, because the flight
record's clock and the profiler's share no origin. In a closed loop at
steady state the window's mean is a fair mean of the slice. None where
no `paged_ragged` event ran (a CPU rehearsal) or the program counts no
tokens."""
from harness import roofline
from harness.paged_attention import DTYPE_BYTES, paged_attention_step

KERNEL = "paged_ragged"
PROGRAM = "serving_mixed_step"


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    recs = [r for r in ctx.flight if "kv_tokens_read" in r]
    if not seconds or not steps or not recs:
        return None
    mean = lambda f: sum(f(r) for r in recs) / len(recs)  # noqa: E731
    kv_read = mean(lambda r: r["kv_tokens_read"])
    pairs = mean(lambda r: r["attn_pairs"])
    queries = mean(lambda r: r["prefill_tokens"] + r["decode_tokens"])
    m, e = ctx.config["model"], ctx.config["engine"]
    heads = m["num_attention_heads"]
    flops, nbytes = paged_attention_step(
        kv_read, pairs, queries, heads=heads,
        head_dim=m["hidden_size"] // heads, layers=m["num_layers"],
        kv_dtype_bytes=DTYPE_BYTES[e["cache_dtype"]],
        act_dtype_bytes=DTYPE_BYTES[m["compute_dtype"]])
    share, bound = roofline.roofline(flops * steps, nbytes * steps,
                                     seconds, ctx.peaks)
    ctx.log(f"paged_ragged roofline: a mean step of the window reads "
            f"{kv_read:.0f} KV tokens and attends {pairs:.0f} pairs "
            f"for {queries:.1f} query tokens ({len(recs)} steps): "
            f"{nbytes / 1e9:.3f} GB and {flops / 1e9:.2f} GFLOP over "
            f"{m['num_layers']} layers, against "
            f"{seconds * 1e3 / steps:.2f} ms of kernel time a step "
            f"over {steps:.0f} steps of the slice; the {bound} bound "
            "applies")
    return share
