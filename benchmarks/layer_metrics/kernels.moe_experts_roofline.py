"""Layer `kernels`: roofline share of the routed experts' grouped
matmuls. The least time the chip needs for the held experts of the
mixed steps of the profiled slice — the larger of FLOPs / peak FLOP/s
and bytes / peak B/s, `harness/moe_experts.py` fed the program's counts
— over the device time of the events whose name contains `moe_experts`.

The work is that of the mean step of the profiled slice (flight records
`moe_pairs_local`, `moe_experts_hit`, both summed over the expert
layers; `harness/flight_slice.py` picks the slice's records by the
bounds the driver hands over, else the window's) times the executions
of the mixed-step program in the slice. None where no such event ran or
the program counts no pairs."""
from harness import flight_slice, roofline
from harness.moe_experts import routed_experts_step
from harness.paged_attention import DTYPE_BYTES

KERNEL = "moe_experts"
PROGRAM = "serving_mixed_step"


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.trace.calls_of(PROGRAM, "modules")
    recs, of = flight_slice.records(ctx, "moe_pairs_local")
    if not seconds or not steps or not recs:
        return None
    pairs = sum(r["moe_pairs_local"] for r in recs) / len(recs)
    hit = sum(r["moe_experts_hit"] for r in recs) / len(recs)
    c = ctx.config
    width = DTYPE_BYTES[c["compute_dtype"]]
    flops, nbytes = routed_experts_step(
        pairs, hit, c["hidden_size"], c["moe_intermediate_size"],
        weight_bytes=width, act_bytes=width)
    share, bound = roofline.roofline(flops * steps, nbytes * steps,
                                     seconds, ctx.peaks)
    ctx.log(f"moe_experts roofline: a mean step of the {of} routes "
            f"{pairs:.0f} pairs to {hit:.1f} held experts over its "
            f"expert layers ({len(recs)} steps): {nbytes / 1e9:.3f} GB "
            f"and {flops / 1e9:.2f} GFLOP, against "
            f"{seconds * 1e3 / steps:.2f} ms of kernel time a step over "
            f"{steps:.0f} steps of the slice; the {bound} bound applies")
    return share
