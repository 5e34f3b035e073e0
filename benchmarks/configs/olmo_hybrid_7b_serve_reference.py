"""Plain reference of `olmo_hybrid_7b_serve`: the Olmo-Hybrid forward
pass in straightforward `jax.numpy` and float32 — no kernel, no cache,
no chunking, no batching, no code of the program under test. One
sequence, every position at once; the delta rule is a `lax.scan` over
the tokens, attention is taken a block of queries at a time and the
bf16 weights are upcast where they are used, so that it fits beside the
engine at the sentinel's 4.6k tokens. That changes what is resident,
not what is computed.

From the source's `config.json` unless marked (+), which is the
family's convention (the Olmo 2/3 block; "Gated Delta Networks", Yang,
Kautz, Hatamizadeh, arXiv:2412.06464, as in the public `fla`
`GatedDeltaNet` layer whose argument names the config's `linear_*` keys
are), listed under `assumed` in the configuration file. h is [T, D];
every norm is `y = x * rsqrt(mean(x^2) + eps) * g`.

    h0     = embed[ids]                                   unscaled
    layer: h = h + norm_attn(mixer(h))                    (+) the norm on
           h = h + norm_mlp(mlp(h))                           each OUTPUT
    mlp:   W_down(silu(W_gate x) * (W_up x)), no biases
    full-attention mixer:
           q, k, v = x Wq, x Wk, x Wv
           q = rms(q) gq; k = rms(k) gk   (+) over all the heads' dims,
                                              before the head split
           heads [T, Hh, Dh]; NO rotary embedding   (+) rope_theta null
           causal softmax(q k^T / sqrt(Dh)) v; out = (.) Wo
    linear-attention mixer, per head of dk, dv:
           q~ k~ v~ = x W_qkv  ([Wq | Wk | Wv] side by side)
           q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
               depthwise causal: y_t[c] = sum_{i<W} w[c, i] u_{t-W+1+i}[c],
               zeros before the sequence, no bias           (+)
           q = q / ||q|| / sqrt(dk); k = k / ||k||   (+) a head, eps 1e-6
                                                         inside the root
           beta_t = 2 sigmoid(x Wb)     the 2 is linear_allow_neg_eigval
           g_t = -exp(A_log) softplus(x Wa + dt_bias); alpha_t = exp(g_t)
           S_t = alpha_t S_{t-1} + k_t u_t^T,
           u_t = beta_t (v_t - (alpha_t S_{t-1})^T k_t),  S_0 = 0
           o_t = S_t^T q_t
           o = rms_head(o) g_o * silu(x Wg)   (+) gated RMSNorm over each
                                                  head's dv, one gain
           out = o.reshape(T, H dv) Wo
    logits = norm_f(h) W_head                             untied

`w` is the model's tree: `embed [V, D]`, `head [D, V]`, `norm_f [D]`,
`layers`: per layer `norm_attn norm_mlp [D]`, `w_gate w_up [D, F]`,
`w_down [F, D]`, and `w_qkv [D, H (2 dk + dv)]`, `wg [D, H dv]`, `wo
[H dv, D]`, `wa wb [D, H]`, `conv_w [H (2 dk + dv), W]`, `A_log dt_bias
[H]`, `o_norm [dv]` (linear) or `wq wk wv wo [D, D]`, `q_norm k_norm
[D]` (full). `cfg`: num_heads, head_dim, linear_heads, linear_key_dim,
linear_value_dim, eps, allow_neg_eigval, layer_kinds ("linear" / "full"
a layer).
"""
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def mm(x, w):
    """Every product with a weight matrix: float32 operands and sum."""
    return jnp.dot(x, w.astype(jnp.float32))


def dots(spec, a, b):
    """The two products of attention (scores, weighted values)."""
    return jnp.einsum(spec, a, b)


def state_dots(spec, a, b):
    """The delta rule's products (the state's reads, its rank-one
    update)."""
    return jnp.einsum(spec, a, b)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def full_attention(lw, x, cfg):
    Hh, Dh, S = cfg["num_heads"], cfg["head_dim"], x.shape[0]
    pos = jnp.arange(S)
    q = rms(mm(x, lw["wq"]), lw["q_norm"], cfg["eps"]).reshape(S, Hh, Dh)
    k = rms(mm(x, lw["wk"]), lw["k_norm"], cfg["eps"]).reshape(S, Hh, Dh)
    v = mm(x, lw["wv"]).reshape(S, Hh, Dh)
    out = []
    # a block of queries at a time: what is resident, not what is
    # computed
    for q0 in range(0, S, QUERY_BLOCK):
        qb, pb = q[q0:q0 + QUERY_BLOCK], pos[q0:q0 + QUERY_BLOCK]
        s = dots("qhd,khd->hqk", qb, k) / math.sqrt(Dh)
        s = jnp.where((pos[None, :] <= pb[:, None])[None], s, -jnp.inf)
        out.append(dots("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return mm(jnp.concatenate(out, 0).reshape(S, Hh * Dh), lw["wo"])


def short_conv(u, w):
    """y_t[c] = sum_i w[c, i] u_{t-W+1+i}[c], zeros before the
    sequence."""
    S, W = u.shape[0], w.shape[1]
    up = jnp.concatenate([jnp.zeros((W - 1, u.shape[1]), u.dtype), u])
    w = w.astype(jnp.float32)
    return sum(up[i:i + S] * w[:, i][None, :] for i in range(W))


def delta_rule(q, k, v, g, beta):
    """q, k [S, H, dk], v [S, H, dv], g, beta [S, H] -> o [S, H, dv]:
    the recurrence, token by token, from the zero state."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        Sd = jnp.exp(gt)[:, None, None] * S
        u = bt[:, None] * (vt - state_dots("hkv,hk->hv", Sd, kt))
        S = Sd + state_dots("hk,hv->hkv", kt, u)
        return S, state_dots("hkv,hk->hv", S, qt)

    return jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))[1]


def linear_attention(lw, x, cfg):
    H, dk, dv = cfg["linear_heads"], cfg["linear_key_dim"], \
        cfg["linear_value_dim"]
    S = x.shape[0]
    y = jax.nn.silu(short_conv(mm(x, lw["w_qkv"]), lw["conv_w"]))
    q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)

    def unit(a):
        a = a.reshape(S, H, dk)
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    beta = jax.nn.sigmoid(mm(x, lw["wb"]))
    if cfg["allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        mm(x, lw["wa"]) + lw["dt_bias"].astype(jnp.float32))
    o = delta_rule(unit(q) / math.sqrt(dk), unit(k), v.reshape(S, H, dv),
                   g, beta)
    o = rms(o, lw["o_norm"], cfg["eps"]).reshape(S, H * dv)
    return mm(o * jax.nn.silu(mm(x, lw["wg"])), lw["wo"])


def logits(w, ids, cfg, last=None):
    """ids [S] -> float32 logits [last or S, V] of the last positions."""
    eps = cfg["eps"]
    with jax.default_matmul_precision("highest"):
        h = w["embed"][ids].astype(jnp.float32)
        for lw, kind in zip(w["layers"], cfg["layer_kinds"]):
            a = linear_attention(lw, h, cfg) if kind == "linear" \
                else full_attention(lw, h, cfg)
            h = h + rms(a, lw["norm_attn"], eps)
            m = mm(jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"]),
                   lw["w_down"])
            h = h + rms(m, lw["norm_mlp"], eps)
        if last:
            h = h[-last:]
        return mm(rms(h, w["norm_f"], eps), w["head"])
