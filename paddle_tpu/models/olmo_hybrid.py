"""Olmo-Hybrid decoder: gated delta-rule ("linear attention") layers
beside full softmax-attention layers, every norm on a sub-layer's
OUTPUT (Olmo 2/3), SwiGLU MLPs, no rotary embedding.

ONE frozen description (`OlmoHybridArch`) and ONE set of layer functions
in `jax.numpy`, as `models/afmoe.py`: the eager `forward` and the
serving engine's mixed step call the same `embed`, `layer_forward` and
`head`, and differ only in the two callbacks they hand in:

- `attend(q, k, v, li)` — a full layer's causal softmax attention; the
  caller owns the K/V cache;
- `recur(x, g, beta, li, conv)` — a linear layer's stateful part. `x
  [T, channels]` are the q~ k~ v~ projections before the short
  convolution, `g`, `beta [T, H]` float32. The caller owns what is kept
  between calls (the recurrent state, the convolution's last inputs),
  the run structure and the padding: it lays each token's last
  `conv_width` inputs side by side, hands them to `conv` (the model's
  depthwise convolution, SiLU and L2 norms, below) and runs the delta
  rule over the q, k, v that come back. -> `o [T, H, dv]`.

The linear mixer, per head of `linear_key_dim` dk / `linear_value_dim`
dv (Gated DeltaNet, arXiv:2412.06464):

    S_t = alpha_t S_{t-1} + k_t u_t^T,  u_t = beta_t (v_t - (alpha_t
    S_{t-1})^T k_t),  o_t = S_t^T q_t,  alpha_t = exp(g_t)

Weights are made on the device, in the compute dtype, from a seed, and
held once (`A_log` and `dt_bias` float32).
"""
from __future__ import annotations

import dataclasses
import math

from .serving_block import ServingBlock

LINEAR, FULL = "linear", "full"


@dataclasses.dataclass(frozen=True)
class OlmoHybridArch:
    hidden_size: int
    num_heads: int              # full layers: query = KV heads
    head_dim: int
    linear_heads: int           # key heads = value heads
    linear_key_dim: int
    linear_value_dim: int
    mlp_width: int
    vocab_rows: int
    layer_kinds: tuple          # LINEAR | FULL a layer
    conv_width: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-6
    max_positions: int = 4096
    compute_dtype: str = "bfloat16"
    delta_chunk: int = 64       # tokens the chunked delta rule takes at once

    # what the serving engine reads of any block's architecture
    window = None

    @property
    def layers(self):
        return self.layer_kinds

    @property
    def num_kv_heads(self):
        return self.num_heads

    @property
    def conv_channels(self):
        """q~, k~ and v~ side by side: what the convolution runs over."""
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)


def arch_from_config(cfg, *, max_positions=None, compute_dtype="bfloat16",
                     delta_chunk=64):
    """An `OlmoHybridArch` from the keys of the source's `config.json`
    (`model_type: olmo_hybrid`)."""
    kinds = {"linear_attention": LINEAR, "full_attention": FULL}
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"] or \
            cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("grouped heads are not built for olmo_hybrid")
    return OlmoHybridArch(
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        head_dim=cfg.get("head_dim") or
        cfg["hidden_size"] // cfg["num_attention_heads"],
        linear_heads=cfg["linear_num_key_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        mlp_width=cfg["intermediate_size"], vocab_rows=cfg["vocab_size"],
        layer_kinds=tuple(kinds[t] for t in cfg["layer_types"]),
        conv_width=cfg["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        eps=cfg["rms_norm_eps"],
        max_positions=max_positions or cfg["max_position_embeddings"],
        compute_dtype=compute_dtype, delta_chunk=delta_chunk)


# ----------------------------------------------------------- the layers


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    """A product with a weight matrix: operands in the compute dtype
    (the weights'), the sum and the result float32."""
    import jax.numpy as jnp
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def embed(arch, w, token_ids):
    """h0 = embed[ids], unscaled: [T] -> [T, D] float32 (the residual
    stream and everything between two products stay float32; the
    products take their operands in the compute dtype)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("embed"):
        return w["embed"][token_ids].astype(jnp.float32)


def short_conv(arch, conv_w, windows):
    """The model's part of `recur`: windows `[T, conv_width, channels]`
    (a token's last inputs, oldest first, zeros before the sequence) ->
    q, k `[T, H, dk]`, v `[T, H, dv]`: the depthwise causal convolution
    `y_t[c] = sum_i w[c, i] u_{t-W+1+i}[c]` (no bias), SiLU, then
    q / ||q|| / sqrt(dk) and k / ||k|| a head."""
    import jax
    import jax.numpy as jnp
    T = windows.shape[0]
    H, dk, dv = arch.linear_heads, arch.linear_key_dim, \
        arch.linear_value_dim
    y = jnp.einsum("twc,cw->tc", windows.astype(jnp.float32),
                   conv_w.astype(jnp.float32))
    y = jax.nn.silu(y)
    q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)

    def unit(x):
        x = x.reshape(T, H, dk)
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    # float32 out: the delta rule takes them as they are (a round trip
    # through the compute dtype here is loss for nothing)
    return unit(q) / math.sqrt(dk), unit(k), v.reshape(T, H, dv)


def linear_mixer(arch, li, lw, x, recur):
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    H, dv = arch.linear_heads, arch.linear_value_dim
    with jax.named_scope("lin_proj"):
        qkv = _mm(x, lw["w_qkv"])                       # [T, channels]
        gate = _mm(x, lw["wg"])
        beta = jax.nn.sigmoid(_mm(x, lw["wb"]))
        if arch.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            _mm(x, lw["wa"]) + lw["dt_bias"].astype(jnp.float32))
    o = recur(qkv, g, beta, li,
              lambda windows: short_conv(arch, lw["conv_w"], windows))
    with jax.named_scope("lin_gate_out"):
        # gated RMSNorm over each head's dv, the gain shared by the heads
        o = _rms(o, lw["o_norm"], arch.eps).reshape(T, H * dv)
        return _mm(o * jax.nn.silu(gate), lw["wo"])


def full_mixer(arch, li, lw, x, attend):
    import jax
    T = x.shape[0]
    Hh, Dh = arch.num_heads, arch.head_dim
    # the norm runs over all the heads' dims at once, before the split
    cd = lw["wq"].dtype     # attention and its cache: the compute dtype
    with jax.named_scope("attn_qkv"):
        q = _rms(_mm(x, lw["wq"]), lw["q_norm"],
                 arch.eps).reshape(T, Hh, Dh).astype(cd)
        k = _rms(_mm(x, lw["wk"]), lw["k_norm"],
                 arch.eps).reshape(T, Hh, Dh).astype(cd)
        v = _mm(x, lw["wv"]).reshape(T, Hh, Dh).astype(cd)
    a = attend(q, k, v, li)
    with jax.named_scope("attn_out"):
        return _mm(a.reshape(T, Hh * Dh), lw["wo"])


def layer_forward(arch, li, lw, h, positions, valid, attend, recur):
    """One decoder layer over flat tokens: h [T, D]. The mixer reads h
    itself; both sub-layers' OUTPUTS are normed. Returns (h, None): the
    block has no counters of its own."""
    import jax
    if arch.layer_kinds[li] == LINEAR:
        a = linear_mixer(arch, li, lw, h, recur)
    else:
        a = full_mixer(arch, li, lw, h, attend)
    # a mixer's output norm and residual count with what made the
    # output: `lin_gate_out` or `attn_out`
    with jax.named_scope("lin_gate_out" if arch.layer_kinds[li] == LINEAR
                         else "attn_out"):
        h = h + _rms(a, lw["norm_attn"], arch.eps)
    with jax.named_scope("mlp"):
        m = _mm(jax.nn.silu(_mm(h, lw["w_gate"])) * _mm(h, lw["w_up"]),
                lw["w_down"])
        return h + _rms(m, lw["norm_mlp"], arch.eps), None


def head(arch, w, h):
    """logits = norm_f(h) W_head (untied), float32."""
    import jax
    with jax.named_scope("head"):
        return _mm(_rms(h, w["norm_f"], arch.eps), w["head"])


def dense_attend(arch, positions):
    """The eager `attend`: the whole sequence against itself, causal."""
    import jax
    import jax.numpy as jnp
    keep = positions[:, None] >= positions[None, :]

    def attend(q, k, v, li):
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(keep[None], s / math.sqrt(arch.head_dim), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return attend


def whole_sequence_recur(arch):
    """The eager `recur`: one sequence from position 0, zeros before
    it, the delta rule token by token from the zero state. No cache."""
    import jax.numpy as jnp

    from ..ops.pallas.gated_delta import gated_delta_scan

    def recur(x, g, beta, li, conv):
        T, W = x.shape[0], arch.conv_width
        xp = jnp.concatenate([jnp.zeros((W - 1,) + x.shape[1:], x.dtype),
                              x])
        windows = jnp.stack([xp[i:i + T] for i in range(W)], axis=1)
        q, k, v = conv(windows)
        zero = jnp.zeros((T,), jnp.int32)
        runs = (jnp.ones((1,), jnp.int32), zero.at[1:].set(T),
                zero.at[0].set(T), zero, zero)
        state = jnp.zeros((1, arch.linear_heads, arch.linear_key_dim,
                           arch.linear_value_dim), jnp.float32)
        return gated_delta_scan(q, k, v, g, beta, runs, state)[0]

    return recur


# ------------------------------------------------------------ the model


def weight_shapes(arch):
    """{name: (shape, init)} of each layer kind and of the top level:
    the one place the parameter layout is written. `init` is a std, None
    for a gain of ones, or the name of a float32 leaf's own rule."""
    D, F = arch.hidden_size, arch.mlp_width
    H, dv = arch.linear_heads, arch.linear_value_dim
    std = 0.02
    both = dict(norm_attn=((D,), None), norm_mlp=((D,), None),
                w_gate=((D, F), std), w_up=((D, F), std),
                w_down=((F, D), std))
    linear = dict(both, w_qkv=((D, arch.conv_channels), std),
                  wg=((D, H * dv), std), wo=((H * dv, D), std),
                  wa=((D, H), std), wb=((D, H), std),
                  conv_w=((arch.conv_channels, arch.conv_width), 0.5),
                  A_log=((H,), "A_log"), dt_bias=((H,), "dt_bias"),
                  o_norm=((dv,), None))
    A = arch.num_heads * arch.head_dim
    full = dict(both, wq=((D, A), std), wk=((D, A), std),
                wv=((D, A), std), wo=((A, D), std),
                q_norm=((A,), None), k_norm=((A,), None))
    top = {"embed": ((arch.vocab_rows, D), std),
           "head": ((D, arch.vocab_rows), std), "norm_f": ((D,), None)}
    return {LINEAR: linear, FULL: full, "top": top}


def init_weights(arch, seed):
    """The parameter tree, made on the default device from `seed`:
    normal with the layout's std in the compute dtype, gains of ones,
    and the two float32 leaves of a linear layer as the public `fla`
    layer initialises them: `A_log = log(uniform(1, 16))`, `dt_bias` the
    inverse softplus of `uniform(0.001, 0.1)` (with a plain normal every
    decay is ~0.5 and no state outlives ten tokens)."""
    import functools

    import jax
    import jax.numpy as jnp
    cd = jnp.dtype(arch.compute_dtype)
    shapes = weight_shapes(arch)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, std, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            dtype)

    base = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    names = sorted({n for group in shapes.values() for n in group})

    def leaf(name, shape, init, salt):
        if init is None:
            return jnp.ones(shape, cd)
        key = jax.random.fold_in(jax.random.fold_in(base, salt),
                                 names.index(name))
        if init == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if init == "dt_bias":
            dt = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
            return dt + jnp.log(-jnp.expm1(-dt))
        return normal(key, shape, float(init), cd)

    w = {n: leaf(n, s, i, 0) for n, (s, i) in shapes["top"].items()}
    w["layers"] = [
        {n: leaf(n, s, i, li + 1) for n, (s, i) in shapes[kind].items()}
        for li, kind in enumerate(arch.layer_kinds)]
    return w


class OlmoHybridForGeneration:
    """The served model: an `OlmoHybridArch`, its weights, the eager
    forward, and the block the serving engine steps."""

    def __init__(self, arch, seed=0, weights=None):
        self.arch = arch
        self.weights = init_weights(arch, seed) if weights is None \
            else weights
        self.vocab_size = arch.vocab_rows
        self.max_position_embeddings = arch.max_positions

    def eval(self):
        return self

    def forward(self, input_ids):
        """Logits [S, V] of one sequence of token ids [S]: the same
        layer functions as the serving step, attention dense, the delta
        rule token by token."""
        import jax.numpy as jnp
        ids = jnp.asarray(input_ids, jnp.int32).reshape(-1)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        valid = jnp.ones(ids.shape, bool)
        h = embed(self.arch, self.weights, ids)
        attend = dense_attend(self.arch, pos)
        recur = whole_sequence_recur(self.arch)
        for li, lw in enumerate(self.weights["layers"]):
            h, _ = layer_forward(self.arch, li, lw, h, pos, valid, attend,
                                 recur)
        return head(self.arch, self.weights, h)

    def serving_block(self):
        return ServingBlock(self.arch, self.weights, embed, layer_forward,
                            head)
