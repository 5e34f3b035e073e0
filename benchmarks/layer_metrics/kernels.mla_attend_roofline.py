"""Layer `kernels`: roofline share of the latent attention of a model
whose layers attend by multi-head latent attention. The least time the
chip needs for that attention in the mixed steps of the profiled slice
— `harness/mla_attention.py` fed the program's counts (flight records
`mla_rows_*`, `mla_pairs_*`, `mla_tokens_read`, summed over the latent
layers) and the configuration's widths: the lesser of the absorbed and
the expanded form, kind by kind — over the device time under the scopes
`attn_latent` (the `mla_attend` kernel and what surrounds it) and
`mla_absorb` (the products with W_UK and W_UV), by the live engine's
own table of instruction -> scope (`harness/device_scopes.py`). The work
is that of the mean step of the profiled slice
(`harness/flight_slice.py`). None where the step sets neither scope or
the program counts no such rows."""
from harness import device_scopes, flight_slice, roofline
from harness.mla_attention import mla_least_step
from harness.paged_attention import DTYPE_BYTES

SCOPES = ("attn_latent", "mla_absorb")


def read(ctx):
    ms = device_scopes.ms_per_step(ctx, *SCOPES)
    recs, of = flight_slice.records(ctx, "mla_tokens_read")
    if not ms or not recs:
        return None
    mean = {f: sum(r[f] for r in recs) / len(recs) for f in (
        "mla_rows_decode", "mla_rows_chunk", "mla_pairs_decode",
        "mla_pairs_chunk", "mla_tokens_read")}
    if not mean["mla_tokens_read"]:
        return None
    c = ctx.config
    cache = DTYPE_BYTES[c["engine"]["cache_dtype"]]
    act = DTYPE_BYTES[c["compute_dtype"]]
    flops, nbytes = mla_least_step(
        mean["mla_rows_decode"], mean["mla_rows_chunk"],
        mean["mla_pairs_decode"], mean["mla_pairs_chunk"],
        mean["mla_tokens_read"], c["num_hidden_layers"],
        c["num_attention_heads"], c["kv_lora_rank"],
        c["qk_rope_head_dim"], c["qk_nope_head_dim"], c["v_head_dim"],
        cache_bytes=cache, act_bytes=act, weight_bytes=act)
    share, bound = roofline.roofline(flops, nbytes, ms / 1e3, ctx.peaks)
    ctx.log(f"mla_attend roofline: a mean step of the {of} ({len(recs)} "
            f"steps) has {mean['mla_rows_decode']:.0f} decode and "
            f"{mean['mla_rows_chunk']:.0f} chunk rows, "
            f"{mean['mla_pairs_decode']:.4g} + "
            f"{mean['mla_pairs_chunk']:.4g} pairs and "
            f"{mean['mla_tokens_read']:.4g} context tokens read over its "
            f"latent layers: {flops / 1e12:.3f} TFLOP and "
            f"{nbytes / 1e9:.3f} GB at the least, against {ms:.3f} ms a "
            f"step under {SCOPES}; the {bound} bound applies")
    return share
