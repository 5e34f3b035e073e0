"""Plain reference of `keye_vl2_30b_a3b_pp8_serve`: the language model of
Keye-VL-2.0 (a Qwen3-MoE block whose attention goes through a learned
selection) in straightforward `jax.numpy` and float32 — no kernel, no
paged cache, no batching, no code of the program under test.

From the source's `config.json` (`sa_config` among its keys) unless
marked (+), which the config does not hold (listed under `assumed` in
the configuration file, read as DeepSeek-V3.2-Exp's released code):

    h0    = embed[ids]
    layer:  h = h + attn(rms(h) g_attn);  h = h + moe(rms(h) g_mlp)
    attn:   q, k, v = x Wq, x Wk, x Wv   (no biases)
            q = rms(q) gq, k = rms(k) gk over each head's dims    (+)
            rotary (theta, all dims, rotate-half) on q, k
            query head i reads KV head i // (Hq / Hkv); 1/sqrt(Dh)
    indexer: qI = x WqI [J, Di];  kI = layernorm(x WkI) gI [Di]   (+)
            rotary on the first `rope_dims` dims of qI and kI     (+)
            w = x Ww [J], times c = J^-0.5 Di^-0.5                (+)
            I(t, s) = sum_j w_t[j] relu(qI_t[j] . kI_s), float32
    selection: S_t = the min(topk, t + 1) positions s <= t with the
            largest I(t, s), by a STABLE sort over the whole row of the
            [S, S] score matrix: equal scores go to the lower position
    MASK:   query t sees key s iff s in S_t; all heads share S_t
            a = (softmax v) Wo
    moe:    p = softmax(x Wr) over all the experts, float32
            idx = top_k(p); w = p[idx] / sum p[idx]   (norm_topk_prob)
            m = sum over the chosen experts of w_k E_idx_k(x),
            E(x) = W_down(silu(W_gate x) * (W_up x))
    logits = rms(h) g_f W_head;  the row at position i predicts token
            i + 1

All norms `x * rsqrt(mean(x^2) + eps) * g` (the layernorm takes the
mean off first). Attention and the selection are taken a block of
query rows at a time, the experts one after the other, each over every
row with the weight the router gave it (0 where it was not chosen):
what is resident, not what is computed.

`forward` runs a whole sequence. `rows` runs N rows at positions
n .. n + N - 1 against a cache of the K, V and indexer keys of the
positions before them (`prefix` makes one from a whole forward over
those positions; under a causal mask they cannot depend on what
follows), so that the comparison can hold a handful of rows against
the reference many times for the price of one whole pass: `rows` over
a `prefix` equals `forward` at those rows
(`tests/test_keye_sparse.py` shows it).

`w` is the model's tree: `embed [V, D]`, `head [D, V]`, `norm_f [D]`,
`layers`: per layer what `sdar_30b_a3b_pp8_serve_reference.py` lists
and `idx_wq [D, J Di]`, `idx_wk [D, Di]`, `idx_ww [D, J]`, `idx_k_norm
[Di]`. `cfg`: num_heads, num_kv_heads, head_dim, eps, rope_theta,
top_k, norm_topk, idx_heads, idx_dim, idx_rope_dims, idx_scale, topk.

Two choices are discontinuous, and at a near-tie either answer is a
correct computation. (a) The router's top-k: as the SDAR reference,
both entries return the router's log-probabilities at the `EDGE` ranks
on either side of the boundary and take `swap`. (b) The selection: both
entries return every row's scores and its selection, and take `select`:
for every layer and row the keys to attend INSTEAD of the reference's
own (a caller feeds the selection of the computation it compares, once
it has seen that the two differ only inside a tie gap).
"""
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
EDGE = 3            # ranks reported on either side of the top-k boundary


def mm(x, w):
    """Every product with a weight matrix: float32 operands and sum."""
    return jnp.dot(x, w.astype(jnp.float32))


def dots(spec, a, b):
    """The products of attention and of the indexer's scores."""
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


def indexer(lw, x, pos, cfg):
    """-> (qI [S, J, Di], kI [S, Di], w [S, J] with the scale in)."""
    J, Di, R = cfg["idx_heads"], cfg["idx_dim"], cfg["idx_rope_dims"]
    S, theta = x.shape[0], cfg["rope_theta"]
    qI = mm(x, lw["idx_wq"]).reshape(S, J, Di)
    kI = mm(x, lw["idx_wk"])
    kI = rms(kI - jnp.mean(kI, -1, keepdims=True), lw["idx_k_norm"],
             cfg["eps"])[:, None, :]
    qI, kI = (jnp.concatenate(
        [rope(a[..., :R], pos, theta), a[..., R:]], -1) for a in (qI, kI))
    w = jnp.dot(x, lw["idx_ww"].astype(jnp.float32)) * cfg["idx_scale"]
    return qI, kI[:, 0], w


def select(score, cand, k):
    """score [T, K] float32, cand [T, K] bool -> keep [T, K]: the
    min(k, candidates) candidates with the largest scores by a STABLE
    sort of the whole row (the keys lie in ascending position), so that
    equal scores go to the lower position."""
    order = jnp.argsort(jnp.where(cand, -score, jnp.inf), axis=1,
                        stable=True)
    rank = jnp.argsort(order, axis=1)        # each key's place in it
    return cand & (rank < k)


def attend(lw, q, qI, w, qpos, k, v, kI, kpos, cfg, given):
    """softmax(q k^T / sqrt(Dh) + M) v, a block of queries at a time,
    M the selection's mask: q [T, Hq, Dh] (+ the indexer's qI, w) at
    `qpos`, keys, values and indexer keys [K, ...] at `kpos`, ascending
    (-1: no key). `given` [T, K] bool or None: the keys to attend in
    place of the reference's own selection. -> (a [T, Hq Dh], scores
    [T, K], keep [T, K] the reference's own selection)."""
    Hq, Hkv, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    T = q.shape[0]
    qg = q.reshape(T, Hkv, Hq // Hkv, Dh)
    out, scores, keeps = [], [], []
    for q0 in range(0, T, QUERY_BLOCK):
        b = slice(q0, q0 + QUERY_BLOCK)
        cand = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[b][:, None])
        sc = jnp.sum(jax.nn.relu(dots("tjd,sd->tjs", qI[b], kI))
                     * w[b][:, :, None], axis=1)
        keep = select(sc, cand, cfg["topk"])
        use = keep if given is None else given[b] & cand
        s = dots("qhgd,khd->hgqk", qg[b], k)
        s = jnp.where(use[None, None], s / math.sqrt(Dh), -jnp.inf)
        out.append(dots("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v))
        scores.append(jnp.where(cand, sc, -jnp.inf))
        keeps.append(keep)
    return (jnp.concatenate(out, 0).reshape(T, Hq * Dh),
            jnp.concatenate(scores, 0), jnp.concatenate(keeps, 0))


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


def _layers(w, ids, pos, cfg, swap, keys_of, given):
    """The layers over rows `ids` at `pos`; `keys_of(li, k, v, kI)`
    gives the keys, values, indexer keys and key positions layer li's
    rows attend. -> (h, [(k, v, kI) of the rows, a layer], edge
    [layers, S, 2R], scores [layers, S, K], keep [layers, S, K])."""
    eps, S = cfg["eps"], ids.shape[0]
    none = jnp.full((S,), -1, jnp.int32)
    h = w["embed"][ids].astype(jnp.float32)
    kvs, edges, scores, keeps = [], [], [], []
    for li, lw in enumerate(w["layers"]):
        x = rms(h, lw["norm_attn"], eps)
        q, k, v = qkv(lw, x, pos, cfg)
        qI, kI, wI = indexer(lw, x, pos, cfg)
        kvs.append((k, v, kI))
        a, sc, keep = attend(lw, q, qI, wI, pos, *keys_of(li, k, v, kI),
                             cfg, None if given is None else given[li])
        h = h + mm(a, lw["wo"])
        m, edge = moe(lw, rms(h, lw["norm_mlp"], eps), cfg,
                      (none, none) if swap is None
                      else (swap[0][li], swap[1][li]))
        edges.append(edge)
        scores.append(sc)
        keeps.append(keep)
        h = h + m
    return h, kvs, jnp.stack(edges), jnp.stack(scores), jnp.stack(keeps)


def head(w, h, cfg):
    return mm(rms(h, w["norm_f"], cfg["eps"]), w["head"])


def forward(w, ids, cfg, last=None, swap=None, select=None):
    """ids [S] -> (float32 logits [last or S, V], edge [layers, last or
    S, 2R], scores and keep [layers, last or S, S]) of a whole
    sequence. `select` [layers, S, S] bool: see the module's text."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        h, _, edge, sc, keep = _layers(
            w, ids, pos, cfg, swap,
            lambda li, k, v, kI: (k, v, kI, pos), select)
        if last:
            h, edge = h[-last:], edge[:, -last:]
            sc, keep = sc[:, -last:], keep[:, -last:]
        return head(w, h, cfg), edge, sc, keep


def logits(w, ids, cfg, last=None):
    return forward(w, ids, cfg, last)[0]


def prefix(w, ids, cfg, length):
    """The K, V and indexer keys of a whole forward over `ids`, as the
    cache `rows` takes: [(K, V, KI) a layer] of `length` rows each, the
    first `len(ids)` filled."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        _, kvs, _, _, _ = _layers(
            w, ids, pos, cfg, None,
            lambda li, k, v, kI: (k, v, kI, pos), None)
    n = length - ids.shape[0]
    return [tuple(jnp.pad(a, ((0, n),) + ((0, 0),) * (a.ndim - 1))
                  for a in kv) for kv in kvs]


def rows(w, cache, n, ids, cfg, swap=None, select=None):
    """N rows `ids` at positions n .. n + N - 1 (n may be traced)
    against `cache`, the K, V and indexer keys of positions [0, n)
    (`prefix`). The keys of a row lie as [the cache's `length` rows, the
    N rows]: `select` [layers, N, length + N] bool and the scores and
    selections returned are in that layout. -> (float32 logits [N, V],
    edge [layers, N, 2R], scores, keep [layers, N, length + N])."""
    N = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        pos = n + jnp.arange(N)
        span = jnp.arange(cache[0][0].shape[0])
        kpos = jnp.concatenate([jnp.where(span < n, span, -1), pos])

        def keys_of(li, k, v, kI):
            K, V, KI = cache[li]
            return (jnp.concatenate([K, k]), jnp.concatenate([V, v]),
                    jnp.concatenate([KI, kI]), kpos)

        h, _, edge, sc, keep = _layers(w, ids, pos, cfg, swap, keys_of,
                                       select)
        return head(w, h, cfg), edge, sc, keep
