"""Token counts -> (FLOPs, bytes) for the attention of the DECODE rows
of one mixed serving step of a model whose layers attend through a
learned selection: each such row attends the K/V tokens of its
selection, gathered (the program counts them for ONE layer: flight
record `sparse_kv_tokens_read`, `sparse_rows_decode`). The same work
whatever implements it, XLA or a kernel; what a token costs and how
many layers there are is counted here, with the benchmark."""
from __future__ import annotations


def sparse_attend_step(kv_tokens_read, rows, layers, heads, kv_heads,
                       head_dim, kv_dtype_bytes=2, act_dtype_bytes=2):
    """Operations and HBM bytes the decode rows' attention of one step
    needs, over `layers` sparse layers.

    Bytes: every selected token's K and V come in once (2 x kv_heads x
    head_dim a token: the query heads of a group share their KV head's
    bytes); every row's Q comes in and its O goes out at `heads` x
    head_dim. FLOPs: q k^T and p v, 4 x heads x head_dim a (row,
    selected token) pair. The indexer's scores and the selection are
    not in it: they have a scope and a metric of their own."""
    kv = kv_tokens_read * 2 * kv_heads * head_dim * kv_dtype_bytes
    qo = rows * 2 * heads * head_dim * act_dtype_bytes
    flops = kv_tokens_read * 4 * heads * head_dim
    return layers * flops, layers * (kv + qo)
