"""Layer `kernels`: roofline share of splash attention in the train
step. Device time of the events whose name contains `splash_mha`
(today `splash_mha_fwd` and the fused backward `splash_mha_dkv`)
against the least time the chip needs for one causal forward and
backward per layer per step: the larger of FLOPs / peak FLOP/s and
bytes / peak B/s, from `harness/roofline.py`. Recomputation is not
counted as work."""
from harness import roofline

KERNEL = "splash_mha"


def read(ctx):
    seconds = ctx.trace.seconds_of(KERNEL)
    steps = ctx.counters.get("profiled_steps")
    if not seconds or not steps:
        return None
    m, t = ctx.config["model"], ctx.traffic
    flops, nbytes = roofline.splash_mha_fwd_bwd(
        batch=t["batch"], heads=m["n_heads"], seq=t["seq_len"],
        head_dim=m["d_model"] // m["n_heads"])
    layers = m["n_layers"] * steps
    share, bound = roofline.roofline(flops * layers, nbytes * layers,
                                     seconds, ctx.peaks)
    ctx.log(f"splash_mha: {seconds * 1e3 / layers:.3f} ms per layer "
            f"forward+backward over {steps} steps x {m['n_layers']} "
            f"layers ({ctx.trace.calls_of(KERNEL):.0f} kernel calls); "
            f"{flops / 1e9:.1f} GFLOP and {nbytes / 1e6:.1f} MB a "
            f"layer; the {bound} bound applies")
    return share
