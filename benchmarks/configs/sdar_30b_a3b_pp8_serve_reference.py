"""Plain reference of `sdar_30b_a3b_pp8_serve`: the SDAR-MoE forward
pass and one denoise pass of its block-diffusion generation, in
straightforward `jax.numpy` and float32 — no kernel, no paged cache, no
batching, no code of the program under test.

From the source's `config.json` unless marked (+), which the config
does not hold (listed under `assumed` in the configuration file):

    h0    = embed[ids]
    layer:  h = h + attn(rms(h) g_attn);  h = h + moe(rms(h) g_mlp)
    attn:   q, k, v = x Wq, x Wk, x Wv   (no biases)
            q = rms(q) gq, k = rms(k) gk over each head's dims    (+)
            rotary (theta, all dims, rotate-half) on q, k
            query head i reads KV head i // (Hq / Hkv); 1/sqrt(Dh)
            MASK block-causal: query i sees key j iff j // L <= i // L
            (L the block length (+)): causal between blocks, both ways
            inside one
            a = (softmax v) Wo
    moe:    p = softmax(x Wr) over all the experts, float32
            idx = top_k(p); w = p[idx] / sum p[idx]   (norm_topk_prob)
            m = sum over the chosen experts of w_k E_idx_k(x),
            E(x) = W_down(silu(W_gate x) * (W_up x))
    logits = rms(h) g_f W_head;  the row at position i predicts token i
                                                              (+) no shift

All norms `x * rsqrt(mean(x^2) + eps) * g`. The experts are taken one
after the other, each over every row with the weight the router gave it
(0 where it was not chosen), so that one expert's float32 weights are
resident at a time: what is resident, not what is computed.

`forward` runs a whole sequence under the dense mask. `denoise_pass`
runs the L rows of ONE block against a cache of the K/V of the blocks
before it (`prefix` makes one from a whole forward; the pass returns
the block's own K/V, which the caller appends once the block is
final): under the mask the rows before a block cannot depend on it, so
this equals the whole forward at every pass (`tests/test_sdar_moe.py`
shows it). The cache has a fixed length and a count, so that one
compiled pass serves every block.

`w` is the model's tree: `embed [V, D]`, `head [D, V]`, `norm_f [D]`,
`layers`: per layer `norm_attn norm_mlp [D]`, `wq [D, Hq Dh]`, `wk wv
[D, Hkv Dh]`, `wo [Hq Dh, D]`, `q_norm k_norm [Dh]`, `router [D, E]`,
`e_gate e_up [E, D, F]`, `e_down [E, F, D]`. `cfg`: num_heads,
num_kv_heads, head_dim, eps, rope_theta, top_k, norm_topk,
block_length.

Top-k is discontinuous: where an expert chosen and one not chosen
score within rounding of each other, either choice is a correct
computation. Both entries therefore also return, for every layer and
row, the router's log-probabilities at the `EDGE` ranks on either side
of the top-k boundary, and take `swap`: per layer and row, the rank
(< k) that gives way and the rank (>= k) that comes in; -1, -1 where
the reference's own choice stands.
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


def router(x, w_router):
    """p = softmax(x Wr) over all the experts."""
    return jax.nn.softmax(jnp.dot(x, w_router.astype(jnp.float32)), -1)


def qkv(lw, x, pos, cfg):
    Hq, Hkv, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    S, eps, theta = x.shape[0], cfg["eps"], cfg["rope_theta"]
    q = rms(mm(x, lw["wq"]).reshape(S, Hq, Dh), lw["q_norm"], eps)
    k = rms(mm(x, lw["wk"]).reshape(S, Hkv, Dh), lw["k_norm"], eps)
    v = mm(x, lw["wv"]).reshape(S, Hkv, Dh)
    return rope(q, pos, theta), rope(k, pos, theta), v


def attend(q, qpos, k, v, kpos, cfg):
    """softmax(q k^T / sqrt(Dh) + M) v under the block-causal mask, a
    block of queries at a time: q [S, Hq, Dh] at `qpos`, keys and
    values [N, Hkv, Dh] at `kpos` (a key position of -1: no key)."""
    Hq, Hkv, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    L, S = cfg["block_length"], q.shape[0]
    qg = q.reshape(S, Hkv, Hq // Hkv, Dh)
    out = []
    for q0 in range(0, S, QUERY_BLOCK):
        qb, pb = qg[q0:q0 + QUERY_BLOCK], qpos[q0:q0 + QUERY_BLOCK]
        keep = (kpos[None, :] >= 0) \
            & (kpos[None, :] // L <= pb[:, None] // L)
        s = dots("qhgd,khd->hgqk", qb, k)
        s = jnp.where(keep[None, None], s / math.sqrt(Dh), -jnp.inf)
        out.append(dots("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, 0).reshape(S, Hq * Dh)


def moe(lw, x, cfg, swap):
    """-> (m [S, D], edge [S, 2R]): the router's log-probabilities at
    ranks k - R .. k + R - 1 (R = min(EDGE, k); the first R are
    chosen). `swap = (out [S], into [S])`, ranks: the expert ranked
    `out` (< k) gives way to the one ranked `into` (>= k)."""
    k, E = cfg["top_k"], lw["e_gate"].shape[0]
    R = min(EDGE, k)
    p = router(x, lw["router"])
    top, idx = jax.lax.top_k(p, k + R)
    out, into = swap
    ranks = jnp.arange(k)[None, :]
    idx = jnp.take_along_axis(
        idx, jnp.where(ranks == out[:, None], into[:, None], ranks), 1)
    wts = jnp.take_along_axis(p, idx, 1)
    if cfg["norm_topk"]:
        wts = wts / wts.sum(-1, keepdims=True)

    def expert(e, m):
        we = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)          # [S]
        y = mm(jax.nn.silu(mm(x, lw["e_gate"][e])) * mm(x, lw["e_up"][e]),
               lw["e_down"][e])
        return m + we[:, None] * y

    return jax.lax.fori_loop(0, E, expert, jnp.zeros_like(x)), \
        jnp.log(top[:, k - R:])


def _layers(w, ids, pos, cfg, swap, keys_of):
    """The layers over rows `ids` at `pos`; `keys_of(li, k, v)` gives
    the keys, values and key positions layer li's rows attend. ->
    (h, [(k, v) of the rows, a layer], edge [layers, S, 2R])."""
    eps, S = cfg["eps"], ids.shape[0]
    none = jnp.full((S,), -1, jnp.int32)
    h = w["embed"][ids].astype(jnp.float32)
    kvs, edges = [], []
    for li, lw in enumerate(w["layers"]):
        q, k, v = qkv(lw, rms(h, lw["norm_attn"], eps), pos, cfg)
        kvs.append((k, v))
        h = h + mm(attend(q, pos, *keys_of(li, k, v), cfg), lw["wo"])
        m, edge = moe(lw, rms(h, lw["norm_mlp"], eps), cfg,
                      (none, none) if swap is None
                      else (swap[0][li], swap[1][li]))
        edges.append(edge)
        h = h + m
    return h, kvs, jnp.stack(edges)


def head(w, h, cfg):
    return mm(rms(h, w["norm_f"], cfg["eps"]), w["head"])


def forward(w, ids, cfg, last=None, swap=None):
    """ids [S] -> (float32 logits [last or S, V], edge [layers, last or
    S, 2R]) under the dense block-causal mask."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        h, _, edge = _layers(w, ids, pos, cfg, swap,
                             lambda li, k, v: (k, v, pos))
        if last:
            h, edge = h[-last:], edge[:, -last:]
        return head(w, h, cfg), edge


def logits(w, ids, cfg, last=None):
    return forward(w, ids, cfg, last)[0]


def prefix(w, ids, cfg, length):
    """The K/V of a whole forward over `ids` (whole blocks), as the
    cache `denoise_pass` takes: [(K, V) a layer] of `length` rows each,
    the first `len(ids)` filled."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        _, kvs, _ = _layers(w, ids, pos, cfg, None,
                            lambda li, k, v: (k, v, pos))
    pad = ((0, length - ids.shape[0]), (0, 0), (0, 0))
    return [(jnp.pad(k, pad), jnp.pad(v, pad)) for k, v in kvs]


def denoise_pass(w, cache, n, ids, cfg, swap=None):
    """One pass over ONE block: `ids [L]`, the block's tokens as fed
    (decided positions as their token, the others as the mask token),
    at positions n .. n + L - 1 (n a multiple of L, may be traced);
    `cache` the K/V of positions [0, n) (`prefix`, then every final
    block appended by `append`). -> (float32 logits [L, V], edge
    [layers, L, 2R], [(k, v) of the block's rows, a layer])."""
    L = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        pos = n + jnp.arange(L)
        span = jnp.arange(cache[0][0].shape[0])

        def keys_of(li, k, v):
            K, V = cache[li]
            return (jnp.concatenate([K, k]), jnp.concatenate([V, v]),
                    jnp.concatenate([jnp.where(span < n, span, -1), pos]))

        h, kvs, edge = _layers(w, ids, pos, cfg, swap, keys_of)
        return head(w, h, cfg), edge, kvs


def append(cache, n, kvs):
    """The cache with a final block's K/V written at rows [n, n + L)."""
    return [(jax.lax.dynamic_update_slice(K, k, (n, 0, 0)),
             jax.lax.dynamic_update_slice(V, v, (n, 0, 0)))
            for (K, V), (k, v) in zip(cache, kvs)]
