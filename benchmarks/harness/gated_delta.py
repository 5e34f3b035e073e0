"""Token and run counts -> (FLOPs, bytes) for the gated delta rule of
one mixed serving step, over all linear layers. The program counts
tokens and runs for ONE linear layer (flight record `lin_tokens`,
`lin_runs`); what they cost, and how many linear layers there are, is
counted here, with the benchmark. The count is of the WORK, whatever
implements it: no chunk size enters it."""
from __future__ import annotations


def gated_delta_step(tokens, runs, layers, heads, key_dim, value_dim,
                     act_bytes=2, state_bytes=4):
    """Operations and HBM bytes the delta rule of one step needs.

    Bytes: a run reads its slot's state `[heads, key_dim, value_dim]`
    once and writes it once; every token's q and k (`key_dim` a head
    each) and v (`value_dim`) come in and its o (`value_dim`) goes out,
    with its two float32 gates g and beta a head. FLOPs, a token a
    head: the decay of the state, S^T k, the rank-one update and S^T q:
    7 x key_dim x value_dim. A kernel that walks padded chunk rows, or
    forms a chunk's triangular system, does more than this: that is its
    cost, not its work."""
    state = runs * 2 * heads * key_dim * value_dim * state_bytes
    rows = tokens * heads * (2 * key_dim + 2 * value_dim) * act_bytes
    gates = tokens * heads * 2 * 4
    flops = tokens * heads * 7 * key_dim * value_dim
    return layers * flops, layers * (state + rows + gates)
