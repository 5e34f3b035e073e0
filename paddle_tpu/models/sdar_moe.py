"""SDAR-MoE decoder (`model_type: sdar_moe`): a Qwen3-MoE block —
pre-norm, grouped queries with an RMSNorm over each head of q and k
before the rotary embedding, a softmax top-k mixture of SwiGLU experts
in every layer, no shared expert — that was trained under a
BLOCK-CAUSAL mask and generates by DIFFUSION OVER BLOCKS.

    h += Attn(RMSNorm(h)); h += MoE(RMSNorm(h)); logits = RMSNorm(h) W

Positions are cut into blocks of L; a query sees the keys up to the
end of its own block. A block is generated by denoise passes over its
L positions (decided ones fed as their token, the others as the MASK
token; the row at position i predicts token i), and one more pass
commits its final K/V (`serving_block.BlockDecoding`).

ONE frozen description (`SdarMoeArch`) and ONE set of layer functions,
as `models/afmoe.py`: the eager `forward` (a whole sequence, dense
block-causal mask) and the serving engine's mixed step call the same
`embed`, `layer_forward` and `head` and differ only in `attend`. The
description says that the model decodes by blocks (`block_decoding`),
as `layer_kinds` says what its layers are; the engine reads both.
`generate()` of the eager model is the block-diffusion procedure in
plain loops, a whole forward pass a denoise pass.

Weights are made on the device, in the compute dtype, from a seed, and
held once.
"""
from __future__ import annotations

import dataclasses
import math

from ..parallel.moe_utils import dropless_expert_ffn, route_softmax_topk
from .afmoe import (STAT_NAMES, _mm, _rms, _rope, fold_stats,
                    seeded_weights)
from .serving_block import BlockDecoding, ServingBlock

FULL = "full"


@dataclasses.dataclass(frozen=True)
class SdarMoeArch:
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_layers: int
    num_experts: int
    top_k: int
    expert_width: int
    vocab_rows: int
    block_decoding: BlockDecoding
    norm_topk: bool = True
    rope_theta: float = 1e6
    eps: float = 1e-6
    max_positions: int = 32768
    compute_dtype: str = "bfloat16"

    # what the serving engine reads of any block's architecture
    window = None

    @property
    def layer_kinds(self):
        return (FULL,) * self.num_layers

    @property
    def layers(self):
        return self.layer_kinds


def arch_from_config(cfg, *, generation, max_positions=None,
                     compute_dtype="bfloat16"):
    """An `SdarMoeArch` from the keys of the source's `config.json`
    (`model_type: sdar_moe`) and `generation`, what the source's
    `generate` call is given and its config does not hold: block_length,
    mask_token_id, denoising_steps, rule, threshold."""
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("sdar_moe with dense layers is not built: every "
                         "layer is an expert layer in the released models")
    return SdarMoeArch(
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_layers=cfg["num_hidden_layers"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        vocab_rows=cfg["vocab_size"],
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        max_positions=max_positions or cfg["max_position_embeddings"],
        compute_dtype=compute_dtype,
        block_decoding=BlockDecoding(
            block_length=int(generation["block_length"]),
            mask_token_id=int(generation["mask_token_id"]),
            denoising_steps=generation.get("denoising_steps"),
            rule=generation.get("rule", "low_confidence_static"),
            threshold=float(generation.get("threshold", 0.9))))


# ----------------------------------------------------------- the layers


def embed(arch, w, token_ids):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("embed"):
        return w["embed"][token_ids].astype(
            jnp.dtype(arch.compute_dtype))


def moe_ffn(arch, lw, x, valid):
    """sum over the top-k experts of weight * expert(x); stats as
    `dropless_expert_ffn` counts them. Every expert is held here."""
    import jax
    with jax.named_scope("moe_router"):
        idx, wts = route_softmax_topk(x, lw["router"], arch.top_k,
                                      norm_topk=arch.norm_topk)
    with jax.named_scope("moe_experts"):
        return dropless_expert_ffn(x, idx, wts, valid, lw["e_gate"],
                                   lw["e_up"], lw["e_down"])


def indexer_proj(arch, lw, x, positions):
    """The indexer's three products of a "sparse" layer
    (`serving_block.LearnedSelection`) over the normed rows x [T, D]:
    -> (qI [T, J, Di] and kI [T, Di] in the compute dtype, the first
    `rope_dims` dims of each rotated at the token's position; w [T, J]
    float32, the constant scale folded in). kI is layer-normed (a gain,
    no bias) before the rotary embedding."""
    import jax.numpy as jnp
    sel = arch.selection
    T, J, Di, R = x.shape[0], sel.num_heads, sel.head_dim, sel.rope_dims
    qI = _mm(x, lw["idx_wq"]).reshape(T, J, Di)
    kf = _mm(x, lw["idx_wk"]).astype(jnp.float32)
    kf = kf - jnp.mean(kf, axis=-1, keepdims=True)
    kI = _rms(kf, lw["idx_k_norm"], sel.eps).astype(x.dtype)[:, None, :]
    qI, kI = (jnp.concatenate(
        [_rope(a[..., :R], positions, arch.rope_theta), a[..., R:]], -1)
        for a in (qI, kI))
    w = jnp.dot(x.astype(jnp.float32), lw["idx_ww"]) * sel.scale
    return qI, kI[:, 0], w


def layer_forward(arch, li, lw, h, positions, valid, attend):
    """One decoder layer over flat tokens: h [T, D], positions [T],
    valid [T] bool. `attend(q, k, v, li)` takes q [T, Hq, Dh] and this
    step's k, v [T, Hkv, Dh] and returns the attention output [T, Hq,
    Dh] of layer `li`: the caller owns the cache and the mask
    (block-causal for SDAR). A layer that holds an indexer (`idx_wq`
    among its weights: a "sparse" layer, `models/keye_vl2.py`) hands
    `attend` its `indexer_proj` as a fifth argument, and the caller
    owns the selection too. Returns (h, MoE stats)."""
    import jax
    T = h.shape[0]
    Hq, Hkv, Dh = arch.num_heads, arch.num_kv_heads, arch.head_dim
    # every operation under one scope of `serving.tracing.DEVICE_SCOPES`
    with jax.named_scope("attn_qkv"):
        x = _rms(h, lw["norm_attn"], arch.eps)
        q = _rms(_mm(x, lw["wq"]).reshape(T, Hq, Dh), lw["q_norm"],
                 arch.eps)
        k = _rms(_mm(x, lw["wk"]).reshape(T, Hkv, Dh), lw["k_norm"],
                 arch.eps)
        v = _mm(x, lw["wv"]).reshape(T, Hkv, Dh)
        q = _rope(q, positions, arch.rope_theta)
        k = _rope(k, positions, arch.rope_theta)
    idx = ()
    if "idx_wq" in lw:
        with jax.named_scope("idx_proj"):
            idx = (indexer_proj(arch, lw, x, positions),)
    a = attend(q, k, v, li, *idx)
    with jax.named_scope("attn_out"):
        h = h + _mm(a.reshape(T, Hq * Dh), lw["wo"])
    # the expert layer's norm and residual count with `mlp`; router and
    # experts keep their own names
    with jax.named_scope("mlp"):
        m, stats = moe_ffn(arch, lw, _rms(h, lw["norm_mlp"], arch.eps),
                           valid)
        return h + m, stats


def head(arch, w, h):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("head"):
        x = _rms(h, w["norm_f"], arch.eps)
        return jnp.dot(x, w["head"].astype(x.dtype),
                       preferred_element_type=jnp.float32)


def dense_attend(arch, positions):
    """The eager `attend`: the whole sequence against itself under the
    dense block-causal mask (key j for query i iff `j // L <= i // L`),
    grouped queries. No cache."""
    import jax
    import jax.numpy as jnp
    L = arch.block_decoding.block_length
    Gq = arch.num_heads // arch.num_kv_heads
    keep = positions[None, :] // L <= positions[:, None] // L

    def attend(q, k, v, li):
        T, Hq, Dh = q.shape
        qg = q.reshape(T, arch.num_kv_heads, Gq, Dh)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(keep[None, None], s / math.sqrt(Dh), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(T, Hq, Dh)

    return attend


# ------------------------------------------------------------ the model


def weight_shapes(arch):
    """{name: (shape, init std or None for a gain of ones)} of a layer
    and of the top level: the one place the layout is written."""
    D, Dh, F = arch.hidden_size, arch.head_dim, arch.expert_width
    Hq, Hkv, E = arch.num_heads, arch.num_kv_heads, arch.num_experts
    std = 0.02
    layer = dict(
        norm_attn=((D,), None), norm_mlp=((D,), None),
        q_norm=((Dh,), None), k_norm=((Dh,), None),
        wq=((D, Hq * Dh), std), wk=((D, Hkv * Dh), std),
        wv=((D, Hkv * Dh), std), wo=((Hq * Dh, D), std),
        router=((D, E), 1.0 / math.sqrt(D)),
        e_gate=((E, D, F), std), e_up=((E, D, F), std),
        e_down=((E, F, D), std))
    top = {"embed": ((arch.vocab_rows, D), std),
           "head": ((D, arch.vocab_rows), std), "norm_f": ((D,), None)}
    return {"layer": layer, "top": top}


def init_weights(arch, seed):
    """The parameter tree, made on the default device from `seed`:
    normal with the layout's std in the compute dtype (the router in
    float32: it scores in float32), gains of ones."""
    return seeded_weights(weight_shapes(arch), ["layer"] * arch.num_layers,
                          seed, arch.compute_dtype, ("router",))


class SdarMoeForGeneration:
    """The served model: an `SdarMoeArch`, its weights, the eager
    forward and `generate`, and the block the serving engine steps."""

    def __init__(self, arch, seed=0, weights=None):
        self.arch = arch
        self.weights = init_weights(arch, seed) if weights is None \
            else weights
        self.vocab_size = arch.vocab_rows
        self.max_position_embeddings = arch.max_positions

    def eval(self):
        return self

    def forward(self, input_ids):
        """Logits [S, V] of one sequence of token ids [S] under the
        dense block-causal mask: the same layer functions as the
        serving step. Row i predicts token i."""
        import jax.numpy as jnp
        ids = jnp.asarray(input_ids, jnp.int32).reshape(-1)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        valid = jnp.ones(ids.shape, bool)
        h = embed(self.arch, self.weights, ids)
        attend = dense_attend(self.arch, pos)
        for li, lw in enumerate(self.weights["layers"]):
            h, _ = layer_forward(self.arch, li, lw, h, pos, valid, attend)
        return head(self.arch, self.weights, h)

    def generate(self, prompt, max_new_tokens, eos_token_id=None):
        """Block-diffusion generation of one sequence, greedy, in plain
        loops: the prompt's whole blocks stand; its last `P mod L`
        tokens start the first generated block as decided positions; a
        denoise pass is a whole forward over everything so far with the
        block's undecided positions fed as the mask token, and decides
        some of them (`BlockDecoding.decide`). No cache, so no commit
        pass: a finished block is simply part of the next forward.
        Returns the first `max_new_tokens` generated tokens (up to and
        with `eos_token_id`)."""
        import jax
        import numpy as np
        bd = self.arch.block_decoding
        L = bd.block_length
        seq = [int(t) for t in prompt]
        start = len(seq) // L * L
        decided = [True] * (len(seq) - start) + [False] * (
            L - (len(seq) - start))
        block = seq[start:] + [bd.mask_token_id] * (L - (len(seq) - start))
        out, done = [], seq[:start]
        while len(out) < max_new_tokens:
            n_pass = 0
            while not all(decided):
                z = np.asarray(self.forward(done + block)[-L:],
                               np.float32)
                cand = z.argmax(-1)
                conf = np.asarray(jax.nn.softmax(z, axis=-1))[
                    np.arange(L), cand]
                masked = [i for i in range(L) if not decided[i]]
                for i in bd.decide(masked, conf, n_pass):
                    block[i], decided[i] = int(cand[i]), True
                n_pass += 1
            out += block[len(seq) + len(out) - len(done):]
            done += block
            block, decided = [bd.mask_token_id] * L, [False] * L
        out = out[:max_new_tokens]
        if eos_token_id is not None and eos_token_id in out:
            out = out[:out.index(eos_token_id) + 1]
        return out

    def serving_block(self):
        return ServingBlock(self.arch, self.weights, embed, layer_forward,
                            head, STAT_NAMES, fold_stats)
