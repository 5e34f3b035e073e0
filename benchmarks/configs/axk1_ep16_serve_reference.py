"""Plain reference of `axk1_ep16_serve`: A.X-K1's forward pass in
straightforward `jax.numpy` and float32 — no kernel, no cache, no
batching, no code of the program under test — with attention in the
EXPANDED form (per-head keys and values made from the latent), so that
it shares nothing of the absorbed algebra it checks. Queries, the dense
FFN and the head are taken a block at a time and the bf16 weights are
upcast where they are used (a slice of a dense weight, one expert, one
layer's attention weights at a time), so that an 8k sentinel fits
beside the engine. That changes what is resident, not what is computed.

From the source's `config.json` and DeepSeek-V3's modeling code (the
family's; listed under `assumed` in the configuration file):

    layer:  x = rms(h) g_attn
            c_q  = rms(x W_qa) g_qa;  q = c_q W_qb  -> [H, Dn + Dr]
            [c | kpe] = x W_kva;  c = rms(c) g_kva
            q_pe, kpe: YaRN rotary at the token's position, the
                interleaved pairs (2i, 2i+1), out as (firsts | seconds)
            k[h] = [c W_UK[h] | kpe], v[h] = c W_UV[h]   (kv_b = [W_UK | W_UV])
            o[h] = softmax(scale q[h] . k[h]) v[h], causal,
                scale = (Dn + Dr)^-0.5 mscale^2
            h = h + concat(o) W_o
            y = rms(h) g_mlp
            dense:  h = h + W_down(silu(W_gate y) * (W_up y))
            moe:    s = sigmoid(y W_r) in float32 (no selection bias)
                    groups: n_group of E / n_group consecutive experts,
                    a group's score the sum of its two largest s; the
                    topk_group best groups' experts are the candidates
                    idx = top_k(s over the candidates); w = s[idx]
                    w = w / sum(w) * routed_scaling_factor
                    h = h + shared(y) + sum over chosen experts HELD HERE
                        of w_k E_idx_k(y)
    logits = rms(h) g_f W_head

All norms `x * rsqrt(mean(x^2) + eps) * g`. The share: this chip holds
experts `[held * rank, held * (rank + 1))`; the router and its groups
run over all of them; what the absent experts would add is left out.

Top-k is discontinuous, twice here: at the top-k boundary among the
candidates, and at the group boundary (a kept group and one left out
change places where their scores tie: a swap there changes E / n_group
candidates at once, and three groups can tie, so the kept group that
gives way need not be the last one kept). `forward` returns, for every
expert layer and position, the scores of the `EDGE` ranks either side
of the expert boundary, whether each is held here, and the group scores
of the `EDGE` ranks either side of the group boundary (fewer where
there are fewer groups); and takes `swap = (out, into, gout, gin)`: at
(expert layer, position), the group ranked `gout` (kept) and the one
ranked `gin` (left out) change places first, then the expert ranked
`out` gives way to the one ranked `into` (ranks of the candidates as
swapped; -1: no swap).

`forward(w, ids, cfg, cache=...)` continues a sequence whose first `n`
positions' latent rows (`[c | kpe]`, a layer) are given: how a caller
makes ONE pass over a long prompt and then passes over the last rows.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
HEAD_GROUP = 8
F_SLICE = 2304
EDGE = 3            # ranks reported on either side of the top-k boundary


def numbers(cfg):
    """`cfg` with what follows from its numbers: the rotary's inverse
    frequencies (`rope_theta`, `rope_scaling`'s factor, original
    positions, beta_fast, beta_slow) and the softmax scale, (Dn +
    Dr)^-0.5 x (0.1 mscale_all_dim ln(factor) + 1)^2. cos and sin carry
    mscale / mscale_all_dim, 1 here (both are 1)."""
    f = cfg["factor"]
    m = 0.1 * cfg["mscale_all_dim"] * math.log(f) + 1.0 if f > 1 else 1.0
    return dict(cfg, inv_freq=tuple(yarn_inv_freq(
        cfg["rope_dim"], cfg["rope_theta"], f, cfg["original_positions"],
        cfg["beta_fast"], cfg["beta_slow"])),
        scale=(cfg["nope_dim"] + cfg["rope_dim"]) ** -0.5 * m * m)


def mm(x, w):
    """Every product with a weight matrix: float32 operands and sum."""
    return jnp.dot(x, w.astype(jnp.float32))


def dots(spec, a, b):
    """Every product of two activations (attention, the up-projections
    of the latent, the output projection of the heads)."""
    return jnp.einsum(spec, a, b)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepSeek-V3's `yarn` inverse frequencies (numpy float64)."""
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra

    def at(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), dim - 1)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low)
                         / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * (1.0 - mask) + extra * mask


def rotary(x, pos, cfg):
    """x [S, ..., Dr] at positions pos [S]: pairs rotated, out as
    (first members | second members)."""
    inv = jnp.asarray(cfg["inv_freq"], jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if cfg.get("rope_layout", "interleaved") == "interleaved":
        a, b = x[..., 0::2], x[..., 1::2]
    else:
        a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def latent_rows(lw, x, pos, cfg):
    """The latent rows `[c | kpe]` [S, Dc + Dr] of tokens x [S, D]."""
    Dc = cfg["kv_lora_rank"]
    kv = mm(x, lw["kv_a"])
    return jnp.concatenate([rms(kv[:, :Dc], lw["kv_a_norm"], cfg["eps"]),
                            rotary(kv[:, Dc:], pos, cfg)], -1)


def attention(lw, x, pos, lat, cfg):
    """h's update by the layer's attention: queries x [T, D] at pos [T]
    over the keys of the latent rows lat [S, Dc + Dr] at positions
    0..S-1, expanded per head, causal. A group of heads at a time (its
    keys and values made from the latent, its queries a block at a
    time), so that one group's float32 weights and keys are resident."""
    H, Dn, Dr, Dv = (cfg[k] for k in ("num_heads", "nope_dim", "rope_dim",
                                      "v_dim"))
    Dc, eps = cfg["kv_lora_rank"], cfg["eps"]
    S, T = lat.shape[0], x.shape[0]
    G = min(HEAD_GROUP, H)
    kvb = lw["kv_b"].reshape(Dc, H, Dn + Dv)
    qb = lw["q_b"].reshape(-1, H, Dn + Dr)
    wo = lw["wo"].reshape(H, Dv, -1)
    cq = rms(mm(x, lw["q_a"]), lw["q_a_norm"], eps)
    keys = jnp.arange(S)
    nb = -(-T // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - T

    def blocks(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (nb, QUERY_BLOCK) + a.shape[1:])

    def group(g, acc):
        def heads(w):
            return jax.lax.dynamic_slice_in_dim(w, g * G, G, axis=1).astype(
                jnp.float32)
        kv = heads(kvb)
        k_nope = dots("sc,chn->shn", lat[:, :Dc], kv[..., :Dn])
        v = dots("sc,chv->shv", lat[:, :Dc], kv[..., Dn:])
        q = dots("tc,chd->thd", cq, heads(qb))
        q_pe = rotary(q[..., Dn:], pos, cfg)

        def block(args):
            qn, qp, pq = args
            s = (dots("qhn,shn->hqs", qn, k_nope)
                 + dots("qhr,sr->hqs", qp, lat[:, Dc:])) * cfg["scale"]
            s = jnp.where((keys[None, :] <= pq[:, None])[None], s, -jnp.inf)
            return dots("hqs,shv->qhv", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, (blocks(q[..., :Dn]), blocks(q_pe),
                                blocks(pos))).reshape(nb * QUERY_BLOCK, G,
                                                      Dv)[:T]
        wo_g = jax.lax.dynamic_slice_in_dim(wo, g * G, G, axis=0)
        return acc + dots("thv,hvd->td", o, wo_g.astype(jnp.float32))

    return jax.lax.fori_loop(0, H // G, group,
                             jnp.zeros((T, wo.shape[-1]), jnp.float32))


def dense_ffn(lw, y):
    """W_down(silu(W_gate y) * W_up y), a slice of the width at a time."""
    F = lw["w_gate"].shape[1]
    n = math.gcd(F, F_SLICE)

    def part(j, out):
        def cols(w, axis):
            return jax.lax.dynamic_slice_in_dim(w, j * n, n, axis=axis)
        g = mm(y, cols(lw["w_gate"], 1))
        u = mm(y, cols(lw["w_up"], 1))
        return out + mm(jax.nn.silu(g) * u, cols(lw["w_down"], 0))

    return jax.lax.fori_loop(0, F // n, part,
                             jnp.zeros(y.shape, jnp.float32))


def swiglu(x, g, u, d):
    return mm(jax.nn.silu(mm(x, g)) * mm(x, u), d)


def group_edge(cfg):
    """The group ranks reported either side of the group boundary: none
    where every group is kept."""
    kg = cfg["topk_group"]
    return min(EDGE, kg, cfg["n_group"] - kg)


def route(lw, y, cfg, swap):
    """-> (idx [S, k] the chosen experts, weights [S, k], edge scores
    [S, 2R], held [S, 2R], group edge scores [S, 2 group_edge])."""
    k, R = cfg["top_k"], min(EDGE, cfg["top_k"])
    ng, kg, RG = cfg["n_group"], cfg["topk_group"], group_edge(cfg)
    here = lw["e_gate"].shape[0]
    s = jax.nn.sigmoid(jnp.dot(y, lw["router"].astype(jnp.float32)))
    S, E = s.shape
    g = s.reshape(S, ng, E // ng)
    gscore = jnp.sum(jax.lax.top_k(g, 2)[0], -1)                 # [S, ng]
    gval, gord = jax.lax.top_k(gscore, ng)
    out, into, gout, gin = swap
    # a kept group and one left out change places
    ranks = jnp.arange(ng)[None, :]
    on = (gout >= 0)[:, None]
    src = jnp.where(on & (ranks == gout[:, None]), gin[:, None],
                    jnp.where(on & (ranks == gin[:, None]), gout[:, None],
                              ranks))
    kept = jnp.take_along_axis(gord, src, 1)[:, :kg]
    mask = jnp.zeros((S, ng), bool).at[jnp.arange(S)[:, None], kept].set(
        True)
    cand = jnp.where(mask[:, :, None], g, -jnp.inf).reshape(S, E)
    top, idx = jax.lax.top_k(cand, k + R)
    held = idx // here == cfg["expert_rank"]
    ranks = jnp.arange(k)[None, :]
    chosen = jnp.take_along_axis(
        idx, jnp.where(ranks == out[:, None], into[:, None], ranks), 1)
    wts = jnp.take_along_axis(s, chosen, 1)
    wts = wts / jnp.sum(wts, -1, keepdims=True) * cfg["route_scale"]
    return chosen, wts, top[:, k - R:], held[:, k - R:], \
        gval[:, kg - RG:kg + RG]


def moe(lw, y, cfg, swap):
    idx, wts, *edge = route(lw, y, cfg, swap)
    here = lw["e_gate"].shape[0]
    m = swiglu(y, lw["s_gate"], lw["s_up"], lw["s_down"])

    def expert(e, m):                       # one expert held here
        glob = here * cfg["expert_rank"] + e
        we = jnp.sum(jnp.where(idx == glob, wts, 0.0), -1)
        return m + we[:, None] * swiglu(
            y, lw["e_gate"][e], lw["e_up"][e], lw["e_down"][e])

    # one after the other: one expert's float32 weights at a time
    return jax.lax.fori_loop(0, here, expert, m), edge


def layer(lw, h, pos, cfg, cache=None, swap=None):
    """One layer over tokens h [T, D] at positions pos [T], the cache's
    latent rows (or none) before them. -> (h, the tokens' latent rows,
    the router's edges or None)."""
    eps = cfg["eps"]
    x = rms(h, lw["norm_attn"], eps)
    own = latent_rows(lw, x, pos, cfg)
    lat = own if cache is None else jnp.concatenate([cache, own], 0)
    h = h + attention(lw, x, pos, lat, cfg)
    y = rms(h, lw["norm_mlp"], eps)
    if "router" not in lw:
        return h + dense_ffn(lw, y), own, None
    if swap is None:
        swap = (jnp.full((h.shape[0],), -1, jnp.int32),) * 4
    m, edge = moe(lw, y, cfg, swap)
    return h + m, own, edge


def forward(w, ids, cfg, cache=None, last=None, swap=None):
    """ids [T] at positions n..n+T-1, where `cache` (default none, n =
    0) holds the first n positions' latent rows, [n, Dc + Dr] a layer.
    -> (float32 logits [last, V] of the last `last` positions (None:
    all; 0: no head), the scores at the expert boundary [expert layers,
    last, 2R], held [the same], the group scores at the group boundary
    [expert layers, last, 2 group_edge], the T positions' latent rows a
    layer). `swap = (out, into, gout, gin)`, int32 [expert layers, T]
    each, or None for the reference's own choice everywhere."""
    T = ids.shape[0]
    n = 0 if cache is None else cache[0].shape[0]
    pos = jnp.arange(n, n + T)
    last = T if last is None else last
    with jax.default_matmul_precision("highest"):
        h = w["embed"][ids].astype(jnp.float32)
        edges, lats = [], []
        for li, lw in enumerate(w["layers"]):
            e = len(edges)
            h, own, edge = layer(
                lw, h, pos, cfg, None if cache is None else cache[li],
                None if swap is None else tuple(a[e] for a in swap))
            lats.append(own)
            if edge is not None:
                edges.append(edge)
        scores, held, groups = (jnp.stack([e[i][-last:] for e in edges])
                                if last else None for i in range(3))
        logits = mm(rms(h[-last:], w["norm_f"], cfg["eps"]), w["head"]) \
            if last else None
        return logits, scores, held, groups, lats


_LAYER_JITS = {}


def prefix(w, ids, cfg):
    """`forward`'s latent rows of ids [n] from position 0, a layer a
    jitted call (h donated from one to the next), so that one layer's
    float32 temporaries are resident at a time beside the engine."""
    key = tuple(sorted(cfg.items()))
    if key not in _LAYER_JITS:
        def one(lw, h, pos):
            with jax.default_matmul_precision("highest"):
                return layer(lw, h, pos, cfg)[:2]
        _LAYER_JITS[key] = jax.jit(one, donate_argnums=1)
    step = _LAYER_JITS[key]
    pos = jnp.arange(ids.shape[0])
    h = jax.jit(lambda e, i: e[i].astype(jnp.float32))(w["embed"], ids)
    lats = []
    for lw in w["layers"]:
        h, own = step(lw, h, pos)
        lats.append(own)
    return lats
