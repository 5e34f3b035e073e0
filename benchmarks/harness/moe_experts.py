"""Pair and expert counts -> (FLOPs, bytes) for the routed experts of
one mixed serving step, over all expert layers. The program counts
pairs and experts (flight record `moe_pairs_local`, `moe_experts_hit`,
both summed over the expert layers); what they cost is counted here,
with the benchmark, so that no PR that claims a gain can change the
count."""
from __future__ import annotations


def routed_experts_step(pairs_local, experts_hit, hidden, expert_width,
                        weight_bytes=2, act_bytes=2):
    """Operations and HBM bytes the held routed experts of one step
    need.

    Bytes: every expert that at least one pair reached reads its three
    SwiGLU matrices once (`experts_hit` x 3 x hidden x expert_width);
    every pair's row comes into each of the two grouped products and
    goes out of it (hidden in and width out, width in and hidden out).
    A kernel that reads an expert's weights once per tile of its rows,
    or the weights of experts no pair reached, does more than this:
    that is its cost, not its work.
    FLOPs: three hidden x width products a pair, 2 a multiply-add."""
    weights = experts_hit * 3 * hidden * expert_width * weight_bytes
    rows = pairs_local * 2 * (hidden + expert_width) * act_bytes
    flops = pairs_local * 6 * hidden * expert_width
    return flops, weights + rows
