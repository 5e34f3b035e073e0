"""Token counts -> (FLOPs, bytes) for the paged attention of one mixed
serving step of a model with grouped queries and window layers. The
program counts tokens for ONE layer of each kind (flight record
`kv_tokens_read_window`, `kv_tokens_read_full`, `attn_pairs_window`,
`attn_pairs_full`, the window already applied); what a token costs and
how many layers of a kind there are is counted here, with the
benchmark."""
from __future__ import annotations


def paged_gqa_step(kv_read, pairs, layers, query_tokens, heads, kv_heads,
                   head_dim, kv_dtype_bytes=2, act_dtype_bytes=2):
    """Operations and HBM bytes the attention of one step needs.

    `kv_read`, `pairs`, `layers`: {"window": n, "full": n}. Bytes: a
    slot fed this step reads the K and V of the keys its queries can
    reach once (2 x kv_heads x head_dim a token); every query token's Q
    comes in and its O goes out at `heads` x head_dim. FLOPs: q k^T and
    p v, 4 x heads x head_dim a (query, key) pair. The query heads of a
    group share their KV head's bytes, not its FLOPs."""
    flops = nbytes = 0
    for kind, n_layers in layers.items():
        kv = kv_read[kind] * 2 * kv_heads * head_dim * kv_dtype_bytes
        qo = query_tokens * 2 * heads * head_dim * act_dtype_bytes
        flops += n_layers * pairs[kind] * 4 * heads * head_dim
        nbytes += n_layers * (kv + qo)
    return flops, nbytes
