"""Layer `kernels`: roofline share of the paged attention kernel in a
model with grouped queries and window layers. The least time the chip
needs for the attention of the mixed steps of the profiled slice —
`harness/paged_attention_gqa.py` fed the program's per-kind token
counts (flight record `kv_tokens_read_window`, `_full`,
`attn_pairs_window`, `_full`: one layer of each kind) and the
configuration's heads and layer kinds — over the device time of the
events whose name contains `paged_ragged`. The work is that of the
mean step of the profiled slice (`harness/flight_slice.py`: the slice's
records by the bounds the driver hands over, else the window's) times
the executions of the mixed-step program in the slice. None where no
such event ran or the program counts no such tokens."""
from harness import flight_slice, roofline
from harness.paged_attention import DTYPE_BYTES
from harness.paged_attention_gqa import paged_gqa_step

KERNEL = "paged_ragged"
PROGRAM = "serving_mixed_step"
KINDS = {"window": "sliding_attention", "full": "full_attention"}


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    recs, of = flight_slice.records(ctx, "kv_tokens_read_window")
    if not seconds or not steps or not recs:
        return None
    mean = lambda f: sum(f(r) for r in recs) / len(recs)  # noqa: E731
    kv_read = {k: mean(lambda r: r[f"kv_tokens_read_{k}"]) for k in KINDS}
    pairs = {k: mean(lambda r: r[f"attn_pairs_{k}"]) for k in KINDS}
    queries = mean(lambda r: r["prefill_tokens"] + r["decode_tokens"])
    c = ctx.config
    layers = {k: c["layer_types"].count(v) for k, v in KINDS.items()}
    flops, nbytes = paged_gqa_step(
        kv_read, pairs, layers, queries,
        heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        kv_dtype_bytes=DTYPE_BYTES[c["engine"]["cache_dtype"]],
        act_dtype_bytes=DTYPE_BYTES[c["compute_dtype"]])
    share, bound = roofline.roofline(flops * steps, nbytes * steps,
                                     seconds, ctx.peaks)
    ctx.log(f"paged attention (grouped queries, window) roofline: a "
            f"mean step of the {of} reads {kv_read['window']:.0f} KV "
            f"tokens a window layer and {kv_read['full']:.0f} a full "
            f"layer, attends {pairs['window']:.0f} and "
            f"{pairs['full']:.0f} pairs, for {queries:.1f} query tokens "
            f"({len(recs)} steps; {layers}): {nbytes / 1e9:.3f} GB and "
            f"{flops / 1e9:.2f} GFLOP, against "
            f"{seconds * 1e3 / steps:.2f} ms of kernel time a step over "
            f"{steps:.0f} steps of the slice; the {bound} bound applies")
    return share
