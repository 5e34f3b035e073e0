"""Layer `kernels`: roofline share of the gated delta rule. The least
time the chip needs for the linear layers' recurrence over the mixed
steps of the profiled slice — the larger of FLOPs / peak FLOP/s and
bytes / peak B/s, `harness/gated_delta.py` fed the program's counts —
over the device time of the events whose name contains `gated_delta`.

The work is that of the mean step of the profiled slice (flight records
`lin_tokens`, `lin_runs`: ONE linear layer; `harness/flight_slice.py`
picks the slice's records by the bounds the driver hands over, else the
window's) times the configuration's linear layers, times the executions
of the mixed-step program in the slice. None where no such event ran or
the program counts no such tokens."""
from harness import flight_slice, roofline
from harness.gated_delta import gated_delta_step
from harness.paged_attention import DTYPE_BYTES

KERNEL = "gated_delta"
PROGRAM = "serving_mixed_step"


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    recs, of = flight_slice.records(ctx, "lin_tokens")
    if not seconds or not steps or not recs:
        return None
    tokens = sum(r["lin_tokens"] for r in recs) / len(recs)
    runs = sum(r["lin_runs"] for r in recs) / len(recs)
    c = ctx.config
    layers = c["layer_types"].count("linear_attention")
    flops, nbytes = gated_delta_step(
        tokens, runs, layers, c["linear_num_key_heads"],
        c["linear_key_head_dim"], c["linear_value_head_dim"],
        act_bytes=DTYPE_BYTES[c["compute_dtype"]])
    share, bound = roofline.roofline(flops * steps, nbytes * steps,
                                     seconds, ctx.peaks)
    ctx.log(f"gated_delta roofline: a mean step of the {of} carries "
            f"{runs:.1f} states through {tokens:.1f} tokens a linear "
            f"layer ({len(recs)} steps; {layers} linear layers): "
            f"{nbytes / 1e9:.3f} GB and {flops / 1e9:.2f} GFLOP, against "
            f"{seconds * 1e3 / steps:.2f} ms of kernel time a step over "
            f"{steps:.0f} steps of the slice; the {bound} bound applies")
    return share
