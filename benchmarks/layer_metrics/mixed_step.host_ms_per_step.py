"""Layer `mixed_step`: median host time of one `engine.step()` in the
window that is NOT the wait for the device: plan + pack + dispatch +
emit + note, the engine's phase fields of the flight record (`ph_*`,
seconds on the engine's clock; `ph_wait`, the readback during which
the device runs, is left out). Logs each phase's median and the three
longest steps of the window with their split and their attention work,
which is where a stalled step shows what it stalled in, and whether it
had more to do. None where the program marks no phases."""
from harness.stats import percentile

HOST = ("ph_plan", "ph_pack", "ph_dispatch", "ph_emit", "ph_note")
ALL = HOST[:3] + ("ph_wait",) + HOST[3:]


def read(ctx):
    recs = [r for r in ctx.flight if "ph_plan" in r]
    if not recs:
        return None
    ms = lambda r, f: r.get(f, 0.0) * 1e3  # noqa: E731
    ctx.log("engine phases ms p50 over %d steps: %s" % (
        len(recs), " ".join(
            f"{f[3:]} {percentile([ms(r, f) for r in recs], 50):.3f}"
            for f in ALL)))
    for r in sorted(recs, key=lambda r: -r["dur"])[:3]:
        ctx.log(f"long step {r['dur'] * 1e3:.2f} ms at ts {r['ts']:.3f} "
                f"(gap before {ms(r, 'gap_before'):.2f}; "
                f"{r.get('kv_tokens_read')} KV tokens read, "
                f"{r.get('attn_pairs')} pairs): " + " ".join(
                    f"{f[3:]} {ms(r, f):.2f}" for f in ALL))
    return percentile([sum(ms(r, f) for f in HOST) for r in recs], 50)
