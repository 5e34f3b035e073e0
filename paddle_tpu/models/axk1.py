"""A.X-K1 (`model_type: axk1`): a DeepSeek-V3-shaped decoder. Every layer
attends by MULTI-HEAD LATENT ATTENTION (DeepSeek-V2, arXiv:2405.04434,
section 2.1); the first `first_k_dense_replace` layers have a dense
SwiGLU, the others a sigmoid-routed mixture of SwiGLU experts under
DeepSeek-V3's group limit (arXiv:2412.19437) beside shared experts.

One layer, x = RMSNorm(h), heads h, a query at t, keys s <= t:

    c_q = RMSNorm(x W_DQ);  q[h] = c_q W_UQ[h] = [q_nope (Dn) | q_pe (Dr)]
    [c_s | kpe_s] = x_s W_DKV;  c_s = RMSNorm(c_s);  kpe_s = yarn(kpe_s, s)
    q_pe = yarn(q_pe, t)
    expanded:  k_s[h] = [c_s W_UK[h] | kpe_s],  v_s[h] = c_s W_UV[h]
               o[h] = softmax_s(scale q[h] . k_s[h]) . v_s[h]
    absorbed:  qa[h] = [W_UK[h] q_nope[h] | q_pe[h]]
               o[h] = W_UV[h] (softmax_s(scale qa[h] . [c_s | kpe_s]) . c_s)
    h += W_O concat_h o[h]

The two forms are equal in exact arithmetic. The eager `forward` runs
the EXPANDED one (`layer_expanded`: per-head K and V made from the
latent, dense causal attention); the serving layer (`layer_forward`)
goes through the engine's `attend`, which caches the 576 numbers `[c |
kpe]` a token and attends each row in the form the engine chose for its
chunk: ABSORBED (the latent outputs, up-projected here) or EXPANDED
from the cached latent rows (the head outputs). The two share every
other line: projections, rotary, FFN, router.

The rotary embedding is YaRN (`yarn_inv_freq`) over the `Dr` rope
dims, rotating the INTERLEAVED pairs (2i, 2i+1) as DeepSeek's code does
once it has reordered `q_pe` / `k_pe`; the rotated rows come out in
that reordered layout (the even members, then the odd), the same for
queries and keys. The MoE pieces are `models/afmoe.py`'s: `MoESpec`
(the chip's share of the experts, the group limit), `moe_ffn` (router,
the held experts, the shared expert), `seeded_weights`, `STAT_NAMES`,
`fold_stats`, `head`.
"""
from __future__ import annotations

import dataclasses
import math

from .afmoe import (STAT_NAMES, MoESpec, _mm, _rms, _swiglu, fold_stats,
                    head, moe_ffn, seeded_weights)
from .serving_block import LatentAttention, ServingBlock

LATENT = "latent"
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class AXK1Arch:
    hidden_size: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_width: int
    vocab_rows: int
    num_layers: int
    first_dense: int
    moe: MoESpec
    yarn: tuple                 # (factor, original positions, beta_fast,
    #                             beta_slow, mscale, mscale_all_dim)
    rope_theta: float = 10000.0
    eps: float = 1e-6
    max_positions: int = 131072
    compute_dtype: str = "bfloat16"

    # what the serving engine reads of any block's architecture
    window = None
    block_decoding = None
    num_kv_heads = 1            # one cached row a token, every head's

    @property
    def layer_kinds(self):
        return (LATENT,) * self.num_layers

    @property
    def layers(self):
        return self.layer_kinds

    @property
    def head_dim(self):
        return self.nope_dim + self.rope_dim

    @property
    def ffn_kinds(self):
        return tuple(DENSE if i < self.first_dense else MOE
                     for i in range(self.num_layers))

    @property
    def scale(self):
        """Softmax scale: (Dn + Dr)^-0.5 x mscale(factor,
        mscale_all_dim)^2 (DeepSeek-V3's YaRN attention)."""
        factor, _, _, _, _, mscale_all = self.yarn
        m = 0.1 * mscale_all * math.log(factor) + 1.0 if factor > 1 \
            else 1.0
        return self.head_dim ** -0.5 * m * m

    @property
    def latent(self):
        return LatentAttention(value_dim=self.kv_lora_rank,
                               rope_dim=self.rope_dim, scale=self.scale,
                               nope_dim=self.nope_dim, v_dim=self.v_dim)


def arch_from_config(cfg, *, experts_held=None, expert_rank=0,
                     vocab_rows=None, max_positions=None,
                     compute_dtype="bfloat16"):
    """An `AXK1Arch` from the keys of the source's `config.json`
    (`model_type: axk1`); `experts_held` / `expert_rank` / `vocab_rows`
    give this chip's share where it is not the whole model.
    `topk_method: "none"` is read as no selection bias; `seq_aux` is a
    training loss and changes nothing served."""
    if cfg.get("topk_method", "none") != "none":
        raise ValueError(f"topk_method {cfg['topk_method']!r}: only "
                         "'none' (no selection bias) is built")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only the sigmoid router is built")
    if int(cfg.get("n_shared_experts", 1)) != 1:
        raise ValueError("one shared expert (moe_ffn's) is built")
    n = cfg["n_routed_experts"]
    ys = cfg["rope_scaling"]
    return AXK1Arch(
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], dense_width=cfg["intermediate_size"],
        vocab_rows=vocab_rows or cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        moe=MoESpec(num_experts=n, top_k=cfg["num_experts_per_tok"],
                    expert_width=cfg["moe_intermediate_size"],
                    experts_held=experts_held or n, expert_rank=expert_rank,
                    route_scale=float(cfg["routed_scaling_factor"]),
                    route_norm=bool(cfg.get("norm_topk_prob", True)),
                    n_group=cfg.get("n_group"),
                    topk_group=cfg.get("topk_group")),
        yarn=(float(ys["factor"]), int(ys["original_max_position_embeddings"]),
              float(ys["beta_fast"]), float(ys["beta_slow"]),
              float(ys.get("mscale", 1)), float(ys.get("mscale_all_dim", 0))),
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        max_positions=max_positions or cfg["max_position_embeddings"],
        compute_dtype=compute_dtype)


# ------------------------------------------------------------ rotary


def yarn_range(dim, base, factor_positions, beta_fast, beta_slow):
    """(low, high) of DeepSeek's `yarn_find_correction_range`: the
    frequency indices at which the ramp from extrapolation to
    interpolation starts and ends."""
    def dim_of(rot):
        return dim * math.log(factor_positions / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low = math.floor(dim_of(beta_fast))
    high = math.ceil(dim_of(beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(dim, base, yarn):
    """The `dim / 2` inverse frequencies of YaRN (numpy float64):
    `f_inter * (1 - m) + f_extra * m`, f_extra = base^(-2i/dim),
    f_inter = f_extra / factor, m = 1 - ramp(low, high); with factor 1
    the plain rotary's."""
    import numpy as np
    factor, orig, beta_fast, beta_slow, _, _ = yarn
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra
    low, high = yarn_range(dim, base, orig, beta_fast, beta_slow)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    m = 1.0 - ramp
    return extra / factor * (1.0 - m) + extra * m


def rope(arch, x, positions):
    """YaRN rotary embedding of x [T, ..., Dr] at positions [T], in
    float32: the interleaved pairs (2i, 2i+1) rotated by position x
    inverse frequency; out as (rotated first members | second members).
    cos and sin carry mscale(factor, mscale) / mscale(factor,
    mscale_all_dim), 1 at this configuration."""
    import jax.numpy as jnp
    factor, _, _, _, ms, ms_all = arch.yarn

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0
    inv = jnp.asarray(yarn_inv_freq(x.shape[-1], arch.rope_theta,
                                    arch.yarn), jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    amp = mscale(ms) / mscale(ms_all)
    cos = (jnp.cos(ang) * amp).reshape(shape)
    sin = (jnp.sin(ang) * amp).reshape(shape)
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


# ----------------------------------------------------------- the layers


def embed(arch, w, token_ids):
    """h0 = embed[ids]: [T] -> [T, D] in the compute dtype."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("embed"):
        return w["embed"][token_ids].astype(jnp.dtype(arch.compute_dtype))


def project(arch, lw, h, positions):
    """The attention's inputs of tokens h [T, D]: (q_nope [T, H, Dn],
    q_pe [T, H, Dr] rotated, c [T, Dc] normed, kpe [T, Dr] rotated)."""
    import jax
    T, H = h.shape[0], arch.num_heads
    with jax.named_scope("attn_qkv"):
        x = _rms(h, lw["norm_attn"], arch.eps)
        cq = _rms(_mm(x, lw["q_a"]), lw["q_a_norm"], arch.eps)
        q = _mm(cq, lw["q_b"]).reshape(T, H, arch.head_dim)
        kv = _mm(x, lw["kv_a"])
        c = _rms(kv[:, :arch.kv_lora_rank], lw["kv_a_norm"], arch.eps)
        kpe = rope(arch, kv[:, arch.kv_lora_rank:], positions)
        q_pe = rope(arch, q[..., arch.nope_dim:], positions)
        return q[..., :arch.nope_dim], q_pe, c, kpe


def up_weights(arch, lw):
    """W_UK [Dc, H, Dn] and W_UV [Dc, H, Dv] of a layer's kv_b."""
    kvb = lw["kv_b"].reshape(arch.kv_lora_rank, arch.num_heads,
                             arch.nope_dim + arch.v_dim)
    return kvb[..., :arch.nope_dim], kvb[..., arch.nope_dim:]


def ffn(arch, li, lw, h, valid):
    """h + the layer's FFN of RMSNorm(h): -> (h, MoE stats or None)."""
    import jax
    with jax.named_scope("mlp"):
        x = _rms(h, lw["norm_mlp"], arch.eps)
        if li < arch.first_dense:
            return h + _swiglu(x, lw["w_gate"], lw["w_up"],
                               lw["w_down"]), None
        m, stats = moe_ffn(arch, lw, x, valid)
        return h + m, stats


def attn_out(arch, lw, h, o):
    """h + W_O concat_h o[h]."""
    import jax
    with jax.named_scope("attn_out"):
        return h + _mm(o.reshape(o.shape[0], -1), lw["wo"])


def layer_forward(arch, li, lw, h, positions, valid, attend):
    """One layer over flat tokens, in the form the engine chose a row:
    the query rows `[W_UK q_nope | q_pe | zeros]` and the tokens' latent
    rows `[c | kpe | zeros]` (`arch.latent.lanes` lanes) go to
    `attend(qa, row, None, li, (q_nope, q_pe, kv_b))`, which owns the
    cache and returns the latent outputs [T, H, Dc] of the rows it
    attended ABSORBED, up-projected by W_UV here, and the head outputs
    of the rows it attended EXPANDED, taken as they are
    (`serving_block.LatentAttention`). -> (h, MoE stats or None)."""
    import jax
    import jax.numpy as jnp
    q_nope, q_pe, c, kpe = project(arch, lw, h, positions)
    w_uk, w_uv = up_weights(arch, lw)
    pad = arch.latent.lanes - arch.kv_lora_rank - arch.rope_dim
    with jax.named_scope("mla_absorb"):
        qa = jnp.einsum("thn,chn->thc", q_nope, w_uk.astype(q_nope.dtype),
                        preferred_element_type=jnp.float32)
        qa = jnp.concatenate(
            [qa.astype(q_pe.dtype), q_pe,
             jnp.zeros(q_pe.shape[:2] + (pad,), q_pe.dtype)], -1)
    with jax.named_scope("attn_qkv"):
        row = jnp.concatenate(
            [c, kpe, jnp.zeros((c.shape[0], pad), c.dtype)], -1)
    lat, o_x, rows_x = attend(qa, row, None, li, (q_nope, q_pe, lw["kv_b"]))
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("thc,chv->thv", lat, w_uv.astype(lat.dtype),
                       preferred_element_type=jnp.float32).astype(h.dtype)
        if rows_x is not None:
            o = jnp.where(rows_x[:, None, None], o_x.astype(h.dtype), o)
    return ffn(arch, li, lw, attn_out(arch, lw, h, o), valid)


def layer_expanded(arch, li, lw, h, positions, valid):
    """One layer of a whole sequence in the EXPANDED form: per-head K
    and V made from the latent, dense causal attention, no cache.
    -> (h, MoE stats or None)."""
    import jax
    import jax.numpy as jnp
    T, H = h.shape[0], arch.num_heads
    q_nope, q_pe, c, kpe = project(arch, lw, h, positions)
    w_uk, w_uv = up_weights(arch, lw)
    k_nope = jnp.einsum("sc,chn->shn", c, w_uk.astype(c.dtype),
                        preferred_element_type=jnp.float32).astype(c.dtype)
    v = jnp.einsum("sc,chv->shv", c, w_uv.astype(c.dtype),
                   preferred_element_type=jnp.float32).astype(c.dtype)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe[:, None], (T, H, arch.rope_dim))], -1)
    s = jnp.einsum("thd,shd->hts", q, k,
                   preferred_element_type=jnp.float32) * arch.scale
    causal = positions[None, :] <= positions[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("hts,shv->thv", p, v,
                   preferred_element_type=jnp.float32).astype(h.dtype)
    return ffn(arch, li, lw, attn_out(arch, lw, h, o), valid)


# ------------------------------------------------------------ the model


def weight_shapes(arch):
    """{group: {name: (shape, init std)}}: the one place the parameter
    layout is written (std None: a gain of ones)."""
    D, H = arch.hidden_size, arch.num_heads
    m, std = arch.moe, 0.02
    attn = {"norm_attn": ((D,), None), "norm_mlp": ((D,), None),
            "q_a": ((D, arch.q_lora_rank), std),
            "q_a_norm": ((arch.q_lora_rank,), None),
            "q_b": ((arch.q_lora_rank, H * arch.head_dim), std),
            "kv_a": ((D, arch.kv_lora_rank + arch.rope_dim), std),
            "kv_a_norm": ((arch.kv_lora_rank,), None),
            "kv_b": ((arch.kv_lora_rank, H * (arch.nope_dim + arch.v_dim)),
                     std),
            "wo": ((H * arch.v_dim, D), std)}
    F, Fm = arch.dense_width, m.expert_width
    dense = dict(attn, w_gate=((D, F), std), w_up=((D, F), std),
                 w_down=((F, D), std))
    moe = dict(attn, router=((D, m.num_experts), 1.0 / math.sqrt(D)),
               s_gate=((D, Fm), std), s_up=((D, Fm), std),
               s_down=((Fm, D), std),
               e_gate=((m.experts_held, D, Fm), std),
               e_up=((m.experts_held, D, Fm), std),
               e_down=((m.experts_held, Fm, D), std))
    top = {"embed": ((arch.vocab_rows, D), std),
           "head": ((D, arch.vocab_rows), std), "norm_f": ((D,), None)}
    return {DENSE: dense, MOE: moe, "top": top}


def init_weights(arch, seed):
    """The parameter tree, made on the default device in the compute
    dtype from `seed`; the router float32 (it feeds a top-k)."""
    return seeded_weights(weight_shapes(arch), list(arch.ffn_kinds), seed,
                          arch.compute_dtype, ("router",))


class AXK1Model:
    """The served model: an `AXK1Arch`, its weights, the eager forward
    and `generate`, and the block the serving engine steps."""

    def __init__(self, arch, seed=0, weights=None):
        self.arch = arch
        self.weights = init_weights(arch, seed) if weights is None \
            else weights
        self.vocab_size = arch.vocab_rows
        self.max_position_embeddings = arch.max_positions

    def eval(self):
        return self

    def forward(self, input_ids):
        """Logits [S, V] of one sequence of token ids [S], causal, the
        attention EXPANDED. Row i predicts token i + 1."""
        import jax.numpy as jnp
        ids = jnp.asarray(input_ids, jnp.int32).reshape(-1)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        valid = jnp.ones(ids.shape, bool)
        h = embed(self.arch, self.weights, ids)
        for li, lw in enumerate(self.weights["layers"]):
            h, _ = layer_expanded(self.arch, li, lw, h, pos, valid)
        return head(self.arch, self.weights, h)

    def generate(self, prompt, max_new_tokens, eos_token_id=None):
        """Greedy generation of one sequence in a plain loop: a whole
        forward a token, no cache."""
        import numpy as np
        seq, out = [int(t) for t in prompt], []
        while len(out) < max_new_tokens:
            out.append(int(np.asarray(self.forward(seq)[-1]).argmax()))
            seq.append(out[-1])
            if out[-1] == eos_token_id:
                break
        return out

    def serving_block(self):
        return ServingBlock(self.arch, self.weights, embed, layer_forward,
                            head, STAT_NAMES, fold_stats)
