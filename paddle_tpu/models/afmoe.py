"""AFMoE (Arcee Trinity) decoder: window and full attention layers with
grouped queries, sandwich RMSNorm, a sigmoid output gate, and a sigmoid
top-k mixture of SwiGLU experts beside one shared expert.

ONE frozen description of the architecture (`AfmoeArch`) and ONE set of
layer functions in `jax.numpy`. The eager `forward` (a whole sequence,
dense masked attention) and the serving engine's mixed step (flat
tokens through the paged cache) call the same `embed`, `layer_forward`
and `head`; the two differ only in the `attend(q, k, v, layer)`
callback they hand in. That triple is the seam `ServingEngine` takes
its block through (`serving_block`).

A model may be one chip's share of an expert-parallel deployment:
`MoESpec.experts_held` of the `num_experts` routed experts live here
(`expert_rank` says which), the router keeps its full width, and the
layer returns its own experts' part of the sum plus the shared expert
(`parallel.moe_utils.dropless_expert_ffn`); `vocab_rows` is the slice
of the vocabulary held. Nothing stands in for the absent chips.

Weights are created on the device, in the compute dtype, from a seed,
and held once (`AfmoeForGeneration.weights`).
"""
from __future__ import annotations

import dataclasses
import math

from ..parallel.moe_utils import dropless_expert_ffn, route_sigmoid_topk
from .serving_block import ServingBlock

SLIDING, FULL = "sliding", "full"
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class MoESpec:
    num_experts: int            # the router's width, as published
    top_k: int
    expert_width: int
    experts_held: int           # routed experts on this chip
    expert_rank: int = 0        # which share: [held * rank, held * (rank + 1))
    route_scale: float = 1.0
    route_norm: bool = True


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    attention: str              # SLIDING | FULL
    ffn: str                    # DENSE | MOE


@dataclasses.dataclass(frozen=True)
class AfmoeArch:
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    dense_width: int
    vocab_rows: int
    layers: tuple
    moe: MoESpec
    rope_theta: float = 10000.0
    eps: float = 1e-5
    max_positions: int = 4096
    compute_dtype: str = "bfloat16"

    @property
    def layer_kinds(self):
        return tuple(l.attention for l in self.layers)


def make_arch(*, layer_types, num_dense_layers, **kw):
    """An `AfmoeArch` from config-style keys: `layer_types` as the
    source spells them (`sliding_attention` / `full_attention`), the
    first `num_dense_layers` layers dense, `moe` a dict of `MoESpec`."""
    kinds = {"sliding_attention": SLIDING, "full_attention": FULL}
    layers = tuple(
        LayerSpec(kinds[t], DENSE if i < num_dense_layers else MOE)
        for i, t in enumerate(layer_types))
    return AfmoeArch(layers=layers, moe=MoESpec(**kw.pop("moe")), **kw)


def arch_from_config(cfg, *, experts_held=None, expert_rank=0,
                     vocab_rows=None, max_positions=None,
                     compute_dtype="bfloat16"):
    """An `AfmoeArch` from the keys of the source's `config.json`
    (`model_type: afmoe`). `experts_held` / `expert_rank` / `vocab_rows`
    give this chip's share where it is not the whole model."""
    n = cfg["num_experts"]
    return make_arch(
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        dense_width=cfg["intermediate_size"],
        vocab_rows=vocab_rows or cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        max_positions=max_positions or cfg["max_position_embeddings"],
        compute_dtype=compute_dtype,
        moe=dict(num_experts=n, top_k=cfg["num_experts_per_tok"],
                 expert_width=cfg["moe_intermediate_size"],
                 experts_held=experts_held or n, expert_rank=expert_rank,
                 route_scale=cfg["route_scale"],
                 route_norm=cfg.get("route_norm", True)))


# ----------------------------------------------------------- the layers


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    import jax.numpy as jnp
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _swiglu(x, w_gate, w_up, w_down):
    import jax
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def _rope(x, positions, theta):
    """Rotate-half rotary positions over the whole head: x [T, H, Dh]."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def embed(arch, w, token_ids):
    """h0 = embed[ids] * sqrt(hidden): [T] -> [T, D] in the compute
    dtype."""
    import jax.numpy as jnp
    import jax
    with jax.named_scope("embed"):
        x = w["embed"][token_ids].astype(jnp.float32)
        return (x * math.sqrt(arch.hidden_size)).astype(
            jnp.dtype(arch.compute_dtype))


def moe_ffn(arch, lw, x, valid):
    """shared(x) + the held experts' part of the routed sum; stats as
    `dropless_expert_ffn` counts them."""
    import jax
    m = arch.moe
    with jax.named_scope("moe_router"):
        idx, wts = route_sigmoid_topk(
            x, lw["router"], lw["expert_bias"], m.top_k,
            route_norm=m.route_norm, route_scale=m.route_scale)
    with jax.named_scope("moe_experts"):
        routed, stats = dropless_expert_ffn(
            x, idx, wts, valid, lw["e_gate"], lw["e_up"], lw["e_down"],
            expert_rank=m.expert_rank)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"])
    return shared + routed, stats


def layer_forward(arch, li, lw, h, positions, valid, attend):
    """One decoder layer over flat tokens: h [T, D], positions [T],
    valid [T] bool. `attend(q, k, v, li)` takes q [T, Hq, Dh] and this
    step's k, v [T, Hkv, Dh] and returns the attention output
    [T, Hq, Dh] of layer `li` (causal; its window if it is a sliding
    layer): the caller owns the cache. Returns (h, MoE stats or None)."""
    import jax
    T = h.shape[0]
    spec = arch.layers[li]
    Hq, Hkv, Dh = arch.num_heads, arch.num_kv_heads, arch.head_dim
    # every operation under one scope of `serving.tracing.DEVICE_SCOPES`
    with jax.named_scope("attn_qkv"):
        x = _rms(h, lw["norm_in"], arch.eps)
        q = _mm(x, lw["wq"]).reshape(T, Hq, Dh)
        k = _mm(x, lw["wk"]).reshape(T, Hkv, Dh)
        v = _mm(x, lw["wv"]).reshape(T, Hkv, Dh)
        gate = _mm(x, lw["wg"])
        q = _rms(q, lw["q_norm"], arch.eps)
        k = _rms(k, lw["k_norm"], arch.eps)
        if spec.attention == SLIDING:   # full layers carry no positions
            q = _rope(q, positions, arch.rope_theta)
            k = _rope(k, positions, arch.rope_theta)
    a = attend(q, k, v, li).reshape(T, Hq * Dh)
    with jax.named_scope("attn_out"):
        a = _mm(a * jax.nn.sigmoid(gate), lw["wo"])
        h = h + _rms(a, lw["norm_post_attn"], arch.eps)
    stats = None
    # the norms either side of the FFN count with `mlp`; an expert
    # layer's router, experts and shared expert keep their own names
    with jax.named_scope("mlp"):
        x = _rms(h, lw["norm_pre_mlp"], arch.eps)
        if spec.ffn == DENSE:
            m = _swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"])
        else:
            m, stats = moe_ffn(arch, lw, x, valid)
        return h + _rms(m, lw["norm_post_mlp"], arch.eps), stats


#: the expert layers' counters of a step, under their flight-record names
STAT_NAMES = ("moe_pairs_total", "moe_pairs_local", "moe_experts_hit",
              "moe_max_expert_pairs")


def fold_stats(acc, st):
    """One expert layer's counters into the step's int32[4]: summed
    over the layers; the fullest expert of any of them."""
    import jax.numpy as jnp
    return jnp.stack([acc[0] + st["pairs_total"],
                      acc[1] + st["pairs_local"],
                      acc[2] + st["experts_hit"],
                      jnp.maximum(acc[3], st["max_expert_pairs"])])


def head(arch, w, h):
    """logits over the held vocabulary rows: norm_f(h) W_head."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        x = _rms(h, w["norm_f"], arch.eps)
        return jnp.dot(x, w["head"].astype(x.dtype),
                       preferred_element_type=jnp.float32)


def dense_attend(arch, positions):
    """The eager `attend`: the whole sequence against itself, causal,
    windowed on sliding layers, grouped queries. No cache."""
    import jax
    import jax.numpy as jnp
    Gq = arch.num_heads // arch.num_kv_heads
    delta = positions[:, None] - positions[None, :]

    def attend(q, k, v, li):
        T, Hq, Dh = q.shape
        keep = delta >= 0
        if arch.layers[li].attention == SLIDING:
            keep &= delta < arch.window
        qg = q.reshape(T, arch.num_kv_heads, Gq, Dh)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(keep[None, None], s / math.sqrt(Dh), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(T, Hq, Dh)

    return attend


# ------------------------------------------------------------ the model


def weight_shapes(arch):
    """{name: (shape, init std, float32?)} of one layer kind, and of the
    top level: the one place the parameter layout is written."""
    D, Dh = arch.hidden_size, arch.head_dim
    Hq, Hkv = arch.num_heads, arch.num_kv_heads
    m = arch.moe
    std = 0.02
    attn = {n: ((D,), None) for n in
            ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")}
    attn.update(q_norm=((Dh,), None), k_norm=((Dh,), None),
                wq=((D, Hq * Dh), std), wk=((D, Hkv * Dh), std),
                wv=((D, Hkv * Dh), std), wg=((D, Hq * Dh), std),
                wo=((Hq * Dh, D), std))
    F, Fm = arch.dense_width, m.expert_width
    dense = dict(attn, w_gate=((D, F), std), w_up=((D, F), std),
                 w_down=((F, D), std))
    moe = dict(attn, router=((D, m.num_experts), 1.0 / math.sqrt(D)),
               expert_bias=((m.num_experts,), 0.02),
               s_gate=((D, Fm), std), s_up=((D, Fm), std),
               s_down=((Fm, D), std),
               e_gate=((m.experts_held, D, Fm), std),
               e_up=((m.experts_held, D, Fm), std),
               e_down=((m.experts_held, Fm, D), std))
    top = {"embed": ((arch.vocab_rows, D), std),
           "head": ((D, arch.vocab_rows), std), "norm_f": ((D,), None)}
    return {DENSE: dense, MOE: moe, "top": top}


#: kept in float32 whatever the compute dtype: the router scores in
#: float32, and its selection bias is compared against those scores
FLOAT32_LEAVES = ("router", "expert_bias")


def seeded_weights(shapes, layer_groups, seed, compute_dtype,
                   float32_leaves=()):
    """A parameter tree made on the default device from `seed`.
    `shapes`: {"top": {name: (shape, std)}, group: {name: (shape,
    std)}}; `layer_groups`: the group of each layer. A std of None is a
    gain of ones; every other leaf is normal with its std, in the
    compute dtype (`float32_leaves` in float32). One small jitted
    generator a distinct shape; a leaf's key is folded from the layer
    and the leaf's name, so a tree does not depend on the order in
    which it is made."""
    import functools

    import jax
    import jax.numpy as jnp
    cd = jnp.dtype(compute_dtype)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, std, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(
            dtype)

    base = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    names = sorted({n for group in shapes.values() for n in group})

    def leaf(name, shape, std, salt):
        if std is None:
            return jnp.ones(shape, cd)
        dtype = jnp.float32 if name in float32_leaves else cd
        key = jax.random.fold_in(jax.random.fold_in(base, salt),
                                 names.index(name))
        return normal(key, shape, float(std), dtype)

    w = {n: leaf(n, s, std, 0) for n, (s, std) in shapes["top"].items()}
    w["layers"] = [
        {n: leaf(n, s, std, li + 1) for n, (s, std) in shapes[g].items()}
        for li, g in enumerate(layer_groups)]
    return w


def init_weights(arch, seed):
    """The parameter tree, made on the default device in the compute
    dtype from `seed`: norms are ones, everything else normal with the
    layout's std (so `expert_bias` is small and non-zero: selection and
    weight differ)."""
    return seeded_weights(weight_shapes(arch),
                          [spec.ffn for spec in arch.layers], seed,
                          arch.compute_dtype, FLOAT32_LEAVES)


class AfmoeForGeneration:
    """The served model: an `AfmoeArch`, its weights, the eager forward,
    and the block the serving engine steps (`serving_block`)."""

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
        layer functions as the serving step, attention dense."""
        import jax.numpy as jnp
        ids = jnp.asarray(input_ids, jnp.int32).reshape(-1)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        valid = jnp.ones(ids.shape, bool)
        h = embed(self.arch, self.weights, ids)
        attend = dense_attend(self.arch, pos)
        for li, lw in enumerate(self.weights["layers"]):
            h, _ = layer_forward(self.arch, li, lw, h, pos, valid, attend)
        return head(self.arch, self.weights, h)

    def serving_block(self):
        """The seam `ServingEngine` steps a model through: the
        architecture, the weight tree (an argument of the jitted step),
        and the three functions of `(arch, weights, ...)` above."""
        return ServingBlock(self.arch, self.weights, embed, layer_forward,
                            head, STAT_NAMES, fold_stats)
