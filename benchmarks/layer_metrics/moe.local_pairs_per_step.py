"""Layer `moe`: (token, choice) pairs a step routed to the experts held
on this chip, summed over its expert layers and averaged over the steps
of the window (flight record `moe_pairs_local`). Logs them against the
pairs routed in all (an even router sends 1 / share of them here), the
held experts reached, and the fullest expert's pairs over the mean of
an expert. None where the program counts no pairs."""


def read(ctx):
    recs = [r for r in ctx.flight if "moe_pairs_local" in r]
    if not recs:
        return None
    n = len(recs)
    local = sum(r["moe_pairs_local"] for r in recs) / n
    total = sum(r["moe_pairs_total"] for r in recs) / n
    hit = sum(r["moe_experts_hit"] for r in recs) / n
    # per step: the fullest expert of any layer over the mean of the
    # experts reached in that step
    skew = [r["moe_max_expert_pairs"]
            / (r["moe_pairs_local"] / r["moe_experts_hit"])
            for r in recs if r["moe_experts_hit"]]
    ctx.log(f"moe: a mean step of the window routes {total:.0f} pairs, "
            f"{local:.1f} of them ({local / max(total, 1):.2%}) to "
            f"experts held here, {hit:.1f} held experts reached (summed "
            f"over the expert layers, {n} steps); the fullest expert "
            f"holds {sum(skew) / max(len(skew), 1):.2f} times the mean "
            "of the experts reached, the largest "
            f"{max(r['moe_max_expert_pairs'] for r in recs)} pairs")
    return local
