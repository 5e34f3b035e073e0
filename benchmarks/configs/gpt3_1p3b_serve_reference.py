"""Plain reference of `gpt3_1p3b_serve`: the GPT-2-architecture forward
pass (Radford et al. 2019; GPT-3 XL widths) in straightforward
`jax.numpy` and float32 — no kernel, no cache, no batching, no code of
the program under test. Learned positions, pre-LayerNorm blocks
(eps 1e-5), fused QKV laid out [3, heads, head_dim] on its output axis,
causal softmax attention, exact (erf) GELU as the served model uses,
final LayerNorm, untied linear head.

`w` is a dict of float32 arrays:
    tok_emb [V, D], pos_emb [P, D],
    ln1_w ln1_b ln2_w ln2_b out_b ffn2_b [L, D],
    qkv_w [L, D, 3D], qkv_b [L, 3D], out_w [L, D, D],
    ffn1_w [L, D, F], ffn1_b [L, F], ffn2_w [L, F, D],
    lnf_w lnf_b [D], head [D, V]
"""
import math

import jax
import jax.numpy as jnp

EPS = 1e-5
LAYER_KEYS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
              "ln2_w", "ln2_b", "ffn1_w", "ffn1_b", "ffn2_w", "ffn2_b")


def layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * w + b


def logits(w, ids, num_heads):
    """ids [S] -> float32 logits [S, V] of every position."""
    with jax.default_matmul_precision("highest"):
        S = ids.shape[0]
        x = w["tok_emb"][ids] + w["pos_emb"][:S]
        D = x.shape[-1]
        dh = D // num_heads
        causal = jnp.tril(jnp.ones((S, S), bool))

        def block(x, lw):
            h = layer_norm(x, lw["ln1_w"], lw["ln1_b"])
            qkv = (h @ lw["qkv_w"] + lw["qkv_b"]).reshape(
                S, 3, num_heads, dh)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            a = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, D)
            x = x + a @ lw["out_w"] + lw["out_b"]
            h = layer_norm(x, lw["ln2_w"], lw["ln2_b"])
            f = jax.nn.gelu(h @ lw["ffn1_w"] + lw["ffn1_b"],
                            approximate=False)
            return x + f @ lw["ffn2_w"] + lw["ffn2_b"], None

        x, _ = jax.lax.scan(block, x, {k: w[k] for k in LAYER_KEYS})
        return layer_norm(x, w["lnf_w"], w["lnf_b"]) @ w["head"]
