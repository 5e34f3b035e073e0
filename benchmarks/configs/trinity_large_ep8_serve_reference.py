"""Plain reference of `trinity_large_ep8_serve`: the AFMoE (Arcee
Trinity) forward pass in straightforward `jax.numpy` and float32 — no
kernel, no cache, no batching, no code of the program under test. One
sequence, every position at once; attention is taken a block of
queries at a time and the bf16 weights are upcast where they are used
(expert by expert), so that it fits beside the engine at the sentinel's
4.6k tokens. That changes what is resident, not what is computed.

From the source's `config.json` unless marked (+), which is from the
public `afmoe` modeling code (listed under `assumed` in the
configuration file):

    h0    = embed[ids] * sqrt(hidden)                        (+) mup
    layer:  h = h + norm_post_attn(attn(norm_in(h)))         (+) sandwich
            h = h + norm_post_mlp(mlp(norm_pre_mlp(h)))
    attn:   q, k, v, g = x Wq, x Wk, x Wv, x Wg  (no biases)
            q = rms(q) gq, k = rms(k) gk over each head      (+)
            sliding layers: rotary (theta, all dims, rotate-half) on
            q, k; full layers: no positions                  (+)
            query head i reads KV head i // (Hq / Hkv); 1/sqrt(Dh);
            causal; sliding: p - window < j <= p
            a = ((softmax v) * sigmoid(g)) Wo                (+) gate
    dense:  W_down(silu(W_gate x) * (W_up x))
    moe:    s = sigmoid(x Wr) in float32
            idx = top_k(s + expert_bias); w = s[idx]         (+) bias
            w = w / (sum w + 1e-20) * route_scale
            m = shared(x) + sum over chosen experts HELD HERE of
                w_k E_idx_k(x)
    logits = norm_f(h) W_head

All norms `x * rsqrt(mean(x^2) + eps) * g`. The share: this chip holds
experts `[held * rank, held * (rank + 1))`; router, top-k and
normalisation run over all the experts; what the absent experts would
add is left out, and that partial result goes on to the next layer.

`w` is the model's tree: `embed [V, D]`, `head [D, V]`, `norm_f [D]`,
`layers`: per layer `norm_in norm_post_attn norm_pre_mlp norm_post_mlp
[D]`, `wq wg [D, Hq Dh]`, `wk wv [D, Hkv Dh]`, `wo [Hq Dh, D]`,
`q_norm k_norm [Dh]`, and `w_gate w_up [D, F]`, `w_down [F, D]` (dense)
or `router [D, E]`, `expert_bias [E]`, `s_gate s_up [D, Fm]`, `s_down
[Fm, D]`, `e_gate e_up [held, D, Fm]`, `e_down [held, Fm, D]` (MoE).
`cfg`: num_heads, num_kv_heads, head_dim, window, eps, rope_theta,
layer_kinds ("sliding" / "full" a layer), top_k, route_scale,
expert_rank.

Top-k is discontinuous: where an expert chosen and one not chosen
score within rounding of each other, either choice is a correct
computation. `logits` therefore also returns, for every expert layer and
position, the selection scores of the `EDGE` ranks on either side of
the top-k boundary and whether each of those experts is held here, and
takes `swap`: the (expert layer, position)s at which one chosen expert
gives way to one that was not, so that a caller can hold a result
against the OTHER correct answers at a near-tie instead of against
none.
"""
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
EDGE = 3            # ranks reported on either side of the top-k boundary


def mm(x, w):
    """Every product with a weight matrix: float32 operands and sum."""
    return jnp.dot(x, w.astype(jnp.float32))


def dots(spec, a, b):
    """The two products of attention (scores, weighted values)."""
    return jnp.einsum(spec, a, b)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def swiglu(x, g, u, d):
    return mm(jax.nn.silu(mm(x, g)) * mm(x, u), d)


def attention(lw, x, cfg, sliding):
    Hq, Hkv, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, S = cfg["eps"], x.shape[0]
    pos = jnp.arange(S)
    q = rms(mm(x, lw["wq"]).reshape(S, Hq, Dh), lw["q_norm"], eps)
    k = rms(mm(x, lw["wk"]).reshape(S, Hkv, Dh), lw["k_norm"], eps)
    v = mm(x, lw["wv"]).reshape(S, Hkv, Dh)
    gate = mm(x, lw["wg"])
    if sliding:
        q, k = rope(q, pos, cfg["rope_theta"]), \
            rope(k, pos, cfg["rope_theta"])
    qg = q.reshape(S, Hkv, Hq // Hkv, Dh)
    out = []
    # a block of queries at a time: what is resident, not what is
    # computed
    for q0 in range(0, S, QUERY_BLOCK):
        qb, pb = qg[q0:q0 + QUERY_BLOCK], pos[q0:q0 + QUERY_BLOCK]
        keep = pos[None, :] <= pb[:, None]
        if sliding:
            keep &= pos[None, :] > pb[:, None] - cfg["window"]
        s = dots("qhgd,khd->hgqk", qb, k)
        s = jnp.where(keep[None, None], s / math.sqrt(Dh), -jnp.inf)
        out.append(dots("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v))
    a = jnp.concatenate(out, 0).reshape(S, Hq * Dh)
    return mm(a * jax.nn.sigmoid(gate), lw["wo"])


def moe(lw, x, cfg, swap):
    """-> (m [S, D], scores [S, 2R], held [S, 2R]): the selection
    scores of ranks k - R .. k + R - 1 (R = min(EDGE, k); the first R
    are chosen) and whether each of those experts is held here.
    `swap = (out [S], into [S])`, ranks: the expert ranked `out` (< k)
    gives way to the one ranked `into` (>= k); -1, -1 where the
    reference's own choice stands."""
    k, here = cfg["top_k"], lw["e_gate"].shape[0]
    R = min(EDGE, k)
    s = jax.nn.sigmoid(jnp.dot(x, lw["router"].astype(jnp.float32)))
    top, idx = jax.lax.top_k(
        s + lw["expert_bias"].astype(jnp.float32), k + R)
    held = idx // here == cfg["expert_rank"]
    out, into = swap
    ranks = jnp.arange(k)[None, :]
    idx = jnp.take_along_axis(
        idx, jnp.where(ranks == out[:, None], into[:, None], ranks), 1)
    wts = jnp.take_along_axis(s, idx, 1)
    wts = wts / (wts.sum(-1, keepdims=True) + 1e-20) * cfg["route_scale"]
    m = swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"])

    def expert(e, m):                       # one expert held here
        glob = here * cfg["expert_rank"] + e
        we = jnp.sum(jnp.where(idx == glob, wts, 0.0), -1)       # [S]
        return m + we[:, None] * swiglu(
            x, lw["e_gate"][e], lw["e_up"][e], lw["e_down"][e])

    # one after the other, so that one expert's float32 weights are
    # resident at a time
    return jax.lax.fori_loop(0, here, expert, m), top[:, k - R:], \
        held[:, k - R:]


def logits(w, ids, cfg, last=None, swap=None):
    """ids [S] -> (float32 logits [last or S, V] of the last positions,
    scores [expert layers, last or S, 2R], held (the same shape), as
    `moe` gives them). `swap = (out, into)`, int32 [expert layers, S]
    each, or None for the reference's own choice everywhere."""
    eps, S = cfg["eps"], ids.shape[0]
    none = jnp.full((S,), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = w["embed"][ids].astype(jnp.float32) * math.sqrt(
            w["embed"].shape[1])
        edges = []
        for lw, kind in zip(w["layers"], cfg["layer_kinds"]):
            a = attention(lw, rms(h, lw["norm_in"], eps), cfg,
                          kind == "sliding")
            h = h + rms(a, lw["norm_post_attn"], eps)
            x = rms(h, lw["norm_pre_mlp"], eps)
            if "router" in lw:
                n = len(edges)
                m, *edge = moe(lw, x, cfg, (none, none) if swap is None
                               else (swap[0][n], swap[1][n]))
                edges.append(edge)
            else:
                m = swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"])
            h = h + rms(m, lw["norm_post_mlp"], eps)
        scores = jnp.stack([e[0] for e in edges])
        held = jnp.stack([e[1] for e in edges])
        if last:
            h, scores, held = h[-last:], scores[:, -last:], held[:, -last:]
        return mm(rms(h, w["norm_f"], eps), w["head"]), scores, held
