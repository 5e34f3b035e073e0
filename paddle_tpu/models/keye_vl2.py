"""The language model of Keye-VL-2.0 (`model_type: KeyeVL2`): a
Qwen3-MoE block — the block `models/sdar_moe.py` holds: pre-norm,
grouped queries with an RMSNorm over each head of q and k before the
rotary embedding, a softmax top-k mixture of SwiGLU experts in every
layer, no shared expert — whose attention goes THROUGH A LEARNED
SELECTION (DeepSeek Sparse Attention over grouped queries):

    a small indexer (`sa_config`: 16 heads of 64, one key head) scores
    every earlier token for every query,
        I(t, s) = sum_j w_t[j] * c * relu(qI_t[j] . kI_s),
    and the softmax runs over the `topk` (2048) best-scoring keys only;
    equal scores go to the lower position; all query heads share the
    selection. A token caches its K, V and its indexer key kI.

The model decodes a token a step under a causal mask. ONE frozen
description (`KeyeArch`) and the Qwen3-MoE layer functions of
`sdar_moe` (`embed`, `layer_forward`, `head`: a layer whose weights hold
an indexer hands `attend` the indexer's three arrays): the eager
`forward` (a whole sequence, the selection as a dense mask) and the
serving engine's mixed step call the same functions and differ only in
`attend`. The description says that its layers are "sparse"
(`layer_kinds`) and what their indexer is (`selection`, a
`serving_block.LearnedSelection`); the engine reads both, as it reads
`window` and `block_decoding`.

The vision tower is NOT here: image tokens would enter as rows of the
embedding with three-component positions (`mrope_section`); under
text-only positions the three components carry the same position and
the rotary embedding is the plain one at `rope_theta`.

Weights are made on the device, in the compute dtype, from a seed, and
held once.
"""
from __future__ import annotations

import dataclasses
import math

from ..ops.pallas.topk_select import index_scores, topk_mask
from . import sdar_moe
from .afmoe import STAT_NAMES, fold_stats, seeded_weights
from .sdar_moe import embed, head, layer_forward
from .serving_block import LearnedSelection, ServingBlock

SPARSE = "sparse"


@dataclasses.dataclass(frozen=True)
class KeyeArch:
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_layers: int
    num_experts: int
    top_k: int
    expert_width: int
    vocab_rows: int
    selection: LearnedSelection
    norm_topk: bool = True
    rope_theta: float = 1e7
    eps: float = 1e-6
    max_positions: int = 262144
    compute_dtype: str = "bfloat16"

    # what the serving engine reads of any block's architecture
    window = None
    block_decoding = None

    @property
    def layer_kinds(self):
        return (SPARSE,) * self.num_layers

    @property
    def layers(self):
        return self.layer_kinds


def arch_from_config(cfg, *, max_positions=None, compute_dtype="bfloat16"):
    """A `KeyeArch` from the language model's keys of the source's
    `config.json` (`sa_config` among them). What the config leaves open
    of the indexer is read as DeepSeek-V3.2-Exp's released code has it:
    half of the indexer's head dims carry the rotary embedding, the
    scale is `heads^-0.5 * head_dim^-0.5`."""
    sa = cfg["sa_config"]
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("an indexer with several key heads is not built")
    J, Di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    return KeyeArch(
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
        selection=LearnedSelection(
            num_heads=J, head_dim=Di, topk=int(sa["topk"]),
            rope_dims=Di // 2,        # `assumed.indexer_rope`: no key of it
            scale=J ** -0.5 * Di ** -0.5, eps=cfg["rms_norm_eps"]))


def dense_attend(arch, positions):
    """The eager `attend`: the whole sequence against itself, causal,
    every query over the keys its indexer selects (a dense [T, T]
    mask), grouped queries. No cache."""
    import jax
    import jax.numpy as jnp
    Gq = arch.num_heads // arch.num_kv_heads
    causal = positions[None, :] <= positions[:, None]

    def attend(q, k, v, li, idx):
        T, Hq, Dh = q.shape
        with jax.named_scope("idx_score"):
            scores = index_scores(idx[0], idx[2], idx[1])
        with jax.named_scope("idx_select"):
            keep = topk_mask(scores, arch.selection.topk, causal)
        with jax.named_scope("attn_sparse"):
            qg = q.reshape(T, arch.num_kv_heads, Gq, Dh)
            s = jnp.einsum("qhgd,khd->hgqk", qg, k,
                           preferred_element_type=jnp.float32)
            s = jnp.where(keep[None, None], s / math.sqrt(Dh), -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(T, Hq, Dh)

    return attend


# ------------------------------------------------------------ the model


def weight_shapes(arch):
    """`sdar_moe.weight_shapes` and, a layer, the indexer's three
    projections and its key norm's gain."""
    shapes = sdar_moe.weight_shapes(arch)
    D, sel = arch.hidden_size, arch.selection
    shapes["layer"].update(
        idx_wq=((D, sel.num_heads * sel.head_dim), 0.02),
        idx_wk=((D, sel.head_dim), 0.02),
        idx_ww=((D, sel.num_heads), 0.02),
        idx_k_norm=((sel.head_dim,), None))
    return shapes


def init_weights(arch, seed):
    """The parameter tree, made on the default device from `seed`: as
    `sdar_moe.init_weights`; the indexer's head weights `idx_ww` are
    float32 like the router (both feed a top-k)."""
    return seeded_weights(weight_shapes(arch), ["layer"] * arch.num_layers,
                          seed, arch.compute_dtype, ("router", "idx_ww"))


class KeyeModel:
    """The served language model: a `KeyeArch`, its weights, the eager
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
        """Logits [S, V] of one sequence of token ids [S], causal, the
        selection as a dense mask: the same layer functions as the
        serving step. Row i predicts token i + 1."""
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
