"""Token counts -> (FLOPs, bytes) for the paged attention of one mixed
serving step, over all layers. The program counts tokens (flight
record `kv_tokens_read`, `attn_pairs`, `prefill_tokens` +
`decode_tokens`); what a token costs is counted here, with the
benchmark, so that no PR that claims a gain can change the count."""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
               "fp8": 1, "float8_e4m3fn": 1}


def paged_attention_step(kv_tokens_read, attn_pairs, query_tokens, heads,
                         head_dim, layers, kv_dtype_bytes=2,
                         act_dtype_bytes=2):
    """Operations and HBM bytes the attention of one step needs.

    Bytes: every slot fed this step reads the K and V of its context
    once (`kv_tokens_read` tokens x 2 x heads x head_dim); every query
    token's Q comes in and its O goes out. The new tokens' K/V are
    written by another op and not counted. A kernel that reads a
    context once per query token, or blocks no token attends, does more
    than this: that is its cost, not its work.
    FLOPs: S = q k^T and o = p v, 2 x head_dim multiply-adds each per
    (query, key) pair per head: 4 x heads x head_dim a pair."""
    width = heads * head_dim
    kv = kv_tokens_read * 2 * width * kv_dtype_bytes
    qo = query_tokens * 2 * width * act_dtype_bytes
    flops = attn_pairs * 4 * width
    return flops * layers, (kv + qo) * layers
