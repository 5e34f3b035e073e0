"""Layer `kernels`: roofline share of the decode rows' attention over
their gathered selections, in a model that attends through a learned
selection. The least time the chip needs for that attention in the
mixed steps of the profiled slice — `harness/sparse_attention.py` fed
the program's counts (flight record `sparse_kv_tokens_read`,
`sparse_rows_decode`: one layer) and the configuration's heads and
layers — over the device time under the scope `attn_sparse` (the
gather, the two products and the softmax, whatever implements them: by
the live engine's own table of instruction -> scope,
`harness/device_scopes.py`). The work is that of the mean step of the
profiled slice (`harness/flight_slice.py`). None where the step sets no
such scope or the program counts no such tokens."""
from harness import device_scopes, flight_slice, roofline
from harness.paged_attention import DTYPE_BYTES
from harness.sparse_attention import sparse_attend_step

SCOPE = "attn_sparse"


def read(ctx):
    ms = device_scopes.ms_per_step(ctx, SCOPE)
    recs, of = flight_slice.records(ctx, "sparse_kv_tokens_read")
    if not ms or not recs:
        return None
    read_ = sum(r["sparse_kv_tokens_read"] for r in recs) / len(recs)
    rows = sum(r["sparse_rows_decode"] for r in recs) / len(recs)
    if not read_:
        return None
    c = ctx.config
    flops, nbytes = sparse_attend_step(
        read_, rows, len(c["layer_types"]),
        heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        kv_dtype_bytes=DTYPE_BYTES[c["engine"]["cache_dtype"]],
        act_dtype_bytes=DTYPE_BYTES[c["compute_dtype"]])
    share, bound = roofline.roofline(flops, nbytes, ms / 1e3, ctx.peaks)
    ctx.log(f"sparse attend roofline: a mean step of the {of} has "
            f"{rows:.1f} decode rows that read {read_:.0f} selected K/V "
            f"tokens a layer ({len(recs)} steps): {nbytes / 1e9:.3f} GB "
            f"and {flops / 1e9:.2f} GFLOP over {len(c['layer_types'])} "
            f"layers, against {ms:.3f} ms a step under `{SCOPE}`; the "
            f"{bound} bound applies")
    return share
