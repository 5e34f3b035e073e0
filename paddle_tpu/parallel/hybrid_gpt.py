"""Hybrid-parallel GPT trainer: dp x pp x mp (+ sequence parallel, + MoE
expert parallel), manual-collective shard_map implementation.

This is the TPU-native equivalent of the reference's dygraph hybrid 3D
parallel path (SURVEY.md §3.6): `HybridCommunicateGroup`
(`fleet/base/topology.py:140`) -> mesh axes; TP layers
(`fleet/layers/mpu/mp_layers.py:39,155,293` Vocab/Column/RowParallel) ->
mp-sharded matmuls with psum/psum_scatter; `PipelineParallel` 1F1B +
`p2p_communication.py` NCCL send/recv -> GPipe microbatch loop over
`lax.ppermute` on the pp mesh axis; `c_softmax_with_cross_entropy_op.cu`
-> vocab-parallel CE with psums; MoE `global_scatter/global_gather`
(`collective/global_scatter_op.cu.cc`) -> `lax.all_to_all` over dp;
sharding stage1/2 (`group_sharded_optimizer_stage2.py:51`) -> ZeRO
reduce-scatter/all-gather of the flattened param vector over dp; recompute
(`fleet/recompute/recompute.py`) -> `jax.checkpoint` on each block.

Sequence parallelism (Megatron-SP style: activations sharded over seq on
the mp axis between blocks, all_gather in / psum_scatter out) is a
first-class extension the reference snapshot lacks (SURVEY.md §5.7).

Everything — forward, backward (jax.grad INSIDE shard_map), grad
reduction, ZeRO-sharded Adam — compiles into ONE XLA executable; the
collectives ride ICI.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis.specs import canonical_sharding
from ..jit.functional import instrumented_jit
from ..profiler import metrics as _metrics
from . import shard_map as _shard_map
from .env import device_grid


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    seq_len: int = 1024
    d_model: int = 2048
    n_heads: int = 16
    n_layers: int = 24
    d_ff: int = 0            # default 4*d_model
    dropout: float = 0.0     # pretraining default
    # parallelism
    dp: int = 1
    pp: int = 1
    mp: int = 1
    ep: int = 1              # expert parallel: experts sharded over a
                             # dedicated "ep" mesh axis; tokens are
                             # data-sharded over (dp, ep) jointly and
                             # shared-param grads psum across ep like dp
    micro_batches: int = 1   # per train_batch, split over pp schedule
    sequence_parallel: bool = False
    # MoE (ISSUE 10): top-k capacity-factor router, fixed [E, C, d]
    # dispatch tensors, all_to_all over "ep" (parallel/moe_utils.py).
    # moe_num_experts is a CONSTRUCTOR-ONLY alias (an InitVar, not a
    # field, and deliberately no read property): dataclasses.replace
    # must see only the one real field, so replace(cfg, moe_experts=0)
    # really produces a dense config instead of the alias
    # resurrecting the expert count
    moe_experts: int = 0     # 0 = dense (alias: moe_num_experts)
    moe_num_experts: dataclasses.InitVar[int] = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01   # load-balance loss weight
    moe_z_weight: float = 1e-3     # router z-loss weight
    # fused residual-add+LN Pallas kernel between attention and FFN
    # (docs/gpt_perf_analysis.md: the XLA add/LN fusions pay carry-layout
    # conversions); jnp fallback off-TPU
    fused_add_ln: bool = True
    # memory / precision
    remat: bool = True
    # None = full per-block recompute; else a jax.checkpoint_policies
    # name (e.g. "dots_with_no_batch_dims_saveable") trading memory for
    # fewer recomputed FLOPs
    remat_policy: Any = None
    # sequence chunks for the vocab CE: the [B,S,V] fp32 logits are the
    # single largest buffer (6.6GB at B=32,S=1024,V=50k) — chunking the
    # head+CE over S with per-chunk remat caps it at 1/N of that
    ce_seq_chunks: int = 1
    # mp=1 fused softmax-CE custom vjp (bf16 logits, recomputed in bwd)
    fused_ce: bool = True
    # python-unrolled layer loop (static slice indices) instead of
    # lax.scan: trades compile time for removing the scan-backward's
    # stacked-gradient dynamic-update-slice traffic
    unroll_layers: bool = False
    # AMP-O2-style step: cast params to compute_dtype once up front and
    # differentiate wrt the bf16 copies — gradients (and the scan-bwd
    # stacked-grad DUS traffic) stay bf16; Adam still updates the f32
    # master params
    bf16_grads: bool = False
    compute_dtype: Any = jnp.bfloat16
    # bucketed + overlapped DP gradient reduction (ISSUE 7): grads are
    # computed per-device INSIDE shard_map, flattened into per-dtype
    # buckets of at most this many bytes, and reduced with ONE psum per
    # bucket — optimization_barrier-chained so XLA can neither combine
    # them back into a single giant all-reduce nor reorder them, which
    # is what lets the TPU async collective scheduler overlap bucket
    # k's wire time with the remaining backward compute. 0 = legacy
    # path (shard_map transpose inserts one psum per parameter leaf).
    # Pure dense-DP only (mp=pp=1, no MoE): other meshes have
    # non-replicated leaves whose grads must NOT be dp-summed.
    grad_bucket_bytes: int = 0
    # optimizer
    learning_rate: float = 1e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    zero_stage: int = 1      # 0: replicated adam; 1: states+update sharded
                             # over dp (stage-2: grads reduce-scattered too)

    def __post_init__(self, moe_num_experts):
        if self.d_ff == 0:
            self.d_ff = 4 * self.d_model
        assert self.n_layers % self.pp == 0
        assert self.n_heads % self.mp == 0
        assert self.d_model % self.n_heads == 0
        assert self.vocab_size % self.mp == 0
        # resolve the constructor alias; refuse two CONFLICTING
        # non-zero values (silently picking one would train the wrong
        # architecture)
        assert not (self.moe_experts and moe_num_experts
                    and self.moe_experts != moe_num_experts), \
            f"moe_experts={self.moe_experts} conflicts with " \
            f"moe_num_experts={moe_num_experts}"
        if moe_num_experts and not self.moe_experts:
            self.moe_experts = moe_num_experts
        if self.moe_experts:
            assert self.moe_experts % self.ep == 0, \
                "moe_experts must divide evenly over the ep axis"
            assert 1 <= self.moe_top_k <= self.moe_experts
        else:
            assert self.ep == 1, \
                "ep > 1 needs a MoE config (dense models scale over dp)"
        if self.sequence_parallel:
            assert self.seq_len % self.mp == 0
        if self.grad_bucket_bytes:
            assert self.mp == 1 and self.pp == 1 \
                and not self.moe_experts, \
                "grad_bucket_bytes needs the pure dense-DP config " \
                "(mp=pp=1, no MoE): only there is every grad leaf " \
                "replicated so a plain dp-psum per bucket is the " \
                "correct reduction"


# --------------------------------------------------------------- params


def init_params(cfg: GPTConfig, key) -> Dict[str, Any]:
    """Full logical parameters (sharding applied by the mesh specs)."""
    k = jax.random.split(key, 16)
    d, ff, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    std = 0.02
    proj_std = std / math.sqrt(2 * L)

    def nrm(key, shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s)

    params = {
        "tok_emb": nrm(k[0], (V, d)),
        "pos_emb": nrm(k[1], (cfg.seq_len, d)),
        "ln_f_w": jnp.ones((d,), jnp.float32),
        "ln_f_b": jnp.zeros((d,), jnp.float32),
        "head": nrm(k[2], (d, V)),
        "blocks": {
            "ln1_w": jnp.ones((L, d), jnp.float32),
            "ln1_b": jnp.zeros((L, d), jnp.float32),
            "w_qkv": nrm(k[3], (L, d, 3 * d)),
            "b_qkv": jnp.zeros((L, 3 * d), jnp.float32),
            "w_o": nrm(k[4], (L, d, d), proj_std),
            "b_o": jnp.zeros((L, d), jnp.float32),
            "ln2_w": jnp.ones((L, d), jnp.float32),
            "ln2_b": jnp.zeros((L, d), jnp.float32),
        },
    }
    if cfg.moe_experts:
        E = cfg.moe_experts
        params["blocks"]["gate"] = nrm(k[5], (L, d, E))
        params["blocks"]["w_fc1"] = nrm(k[6], (L, E, d, ff))
        params["blocks"]["b_fc1"] = jnp.zeros((L, E, ff), jnp.float32)
        params["blocks"]["w_fc2"] = nrm(k[7], (L, E, ff, d), proj_std)
        params["blocks"]["b_fc2"] = jnp.zeros((L, E, d), jnp.float32)
    else:
        params["blocks"]["w_fc1"] = nrm(k[6], (L, d, ff))
        params["blocks"]["b_fc1"] = jnp.zeros((L, ff), jnp.float32)
        params["blocks"]["w_fc2"] = nrm(k[7], (L, ff, d), proj_std)
        params["blocks"]["b_fc2"] = jnp.zeros((L, d), jnp.float32)
    return params


def param_specs(cfg: GPTConfig) -> Dict[str, Any]:
    """PartitionSpec per leaf: pp shards the stacked layer dim, mp shards
    head/ffn/vocab dims, everything else replicated (dp replicates params;
    ZeRO shards the *optimizer* state instead)."""
    moe = cfg.moe_experts > 0
    blocks = {
        "ln1_w": P("pp", None), "ln1_b": P("pp", None),
        "w_qkv": P("pp", None, "mp"), "b_qkv": P("pp", "mp"),
        "w_o": P("pp", "mp", None), "b_o": P("pp", None),
        "ln2_w": P("pp", None), "ln2_b": P("pp", None),
    }
    if moe:
        # experts sharded over the dedicated ep axis (gate is a SHARED
        # param: replicated over dp AND ep, so the shard_map transpose
        # psums its grad across both — the "like dp" contract)
        blocks.update({
            "gate": P("pp", None, None),
            "w_fc1": P("pp", "ep", None, "mp"),
            "b_fc1": P("pp", "ep", "mp"),
            "w_fc2": P("pp", "ep", "mp", None),
            "b_fc2": P("pp", "ep", None),
        })
    else:
        blocks.update({
            "w_fc1": P("pp", None, "mp"), "b_fc1": P("pp", "mp"),
            "w_fc2": P("pp", "mp", None), "b_fc2": P("pp", None),
        })
    return {
        "tok_emb": P("mp", None),
        "pos_emb": P(None, None),
        "ln_f_w": P(None), "ln_f_b": P(None),
        "head": P(None, "mp"),
        "blocks": blocks,
    }


# ----------------------------------------------------------- model math


def _layer_norm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) / jnp.sqrt(var + eps) * w + b).astype(x.dtype)


def _attention(x, w_qkv, b_qkv, w_o, b_o, cfg: GPTConfig):
    """x [B, S, d] (full seq, mp-local heads). Causal self-attention.

    TPU: splash Pallas flash kernel (fwd + fused dkv/dq backward) —
    trace-measured 2.1x faster fwd+bwd than XLA's fused attention at
    [32,16,1024,64]; lifted the 350M single-chip headline 23.5k -> 33.9k
    tok/s (docs/gpt_perf_analysis.md). Off-TPU (CPU test mesh): XLA's
    fused attention, which never materializes the [S,S] probs either.
    """
    from ..ops.pallas.flash_attention import splash_mha
    d = x.shape[-1]
    h_loc = cfg.n_heads // cfg.mp
    hd = cfg.d_model // cfg.n_heads
    cd = cfg.compute_dtype
    xc = x.astype(cd)
    # [B, H, S, Dh] straight out of three per-tensor projections
    # ("bsd,dhe->bhse"): r5 traces show the old plain-matmul +
    # transpose pattern no longer fuses (6x ~8-10ms relayout copies).
    # Each head's output N-tile is 64 wide, half the MXU's lanes: a
    # fused head-pair Pallas projection measured no better in the model
    # (docs/gpt_perf_analysis.md)
    wq, wk, wv = jnp.split(w_qkv.astype(cd), 3, axis=-1)
    bq, bk, bv = jnp.split(b_qkv.astype(cd), 3, axis=-1)

    def proj(w, b):
        out = jnp.einsum("bsd,dhe->bhse", xc, w.reshape(d, h_loc, hd))
        return out + b.reshape(h_loc, 1, hd)
    q, k_, v = proj(wq, bq), proj(wk, bk), proj(wv, bv)
    ctx = splash_mha(q, k_, v, causal=True, scale=1.0 / math.sqrt(hd),
                     save_residuals_for_remat=(
                         cfg.remat_policy == "save_splash_residuals"))
    out = jnp.einsum("bhse,hed->bsd", ctx.astype(cd),
                     w_o.astype(cd).reshape(h_loc, hd, d))
    # row-parallel: partial sums over mp; reduction by caller
    return out, b_o


def _dense_ffn(x, w1, b1, w2, b2, cfg: GPTConfig):
    cd = cfg.compute_dtype
    h = jnp.einsum("bsd,df->bsf", x.astype(cd), w1.astype(cd)) \
        + b1.astype(cd)
    h = jax.nn.gelu(h)
    out = jnp.einsum("bsf,fd->bsd", h, w2.astype(cd))
    return out, b2


def _moe_data_axes(cfg: GPTConfig):
    """Mesh axes the token batch is sharded over (None outside a
    multi-rank mesh): the axes MoE routing statistics must psum across
    for EP/DP-invariant aux losses and global expert counts."""
    axes = tuple(a for a, n in (("dp", cfg.dp), ("ep", cfg.ep)) if n > 1)
    return axes or None


def _zero_moe_stats(cfg: GPTConfig):
    """The per-block MoE stats pytree (dense blocks contribute zeros so
    the scan carry keeps one static structure)."""
    E = max(cfg.moe_experts, 1)
    return {"balance": jnp.zeros((), jnp.float32),
            "z": jnp.zeros((), jnp.float32),
            "counts": jnp.zeros((E,), jnp.float32),
            "dropped": jnp.zeros((), jnp.float32)}


def _moe_ffn(x, gate_w, w1, b1, w2, b2, cfg: GPTConfig):
    """Top-k capacity-factor MoE with expert parallelism over "ep".

    x [B, S, d] local tokens. Experts: E total, E/ep resident per ep
    rank (w1 local [E_loc, d, ff_loc]). Routing/dispatch/combine come
    from `parallel.moe_utils` (fixed one-hot einsums); the [E, C, d]
    dispatch tensor rides `lax.all_to_all` over "ep" to the expert
    owners and back (the compiled global_scatter/global_gather).
    Capacity-overflowed (token, choice) pairs contribute 0 — the
    block's residual connection is the drop path. Returns
    (out_partial_over_mp, stats) with stats per `_zero_moe_stats`
    (balance/z losses are psum'd over the data axes so they are
    invariant to the dp x ep token sharding)."""
    from . import moe_utils
    cd = cfg.compute_dtype
    B, S, d = x.shape
    T = B * S
    E = cfg.moe_experts
    ep = cfg.ep
    axes = _moe_data_axes(cfg)
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        gate_w.astype(jnp.float32))
    C = moe_utils.expert_capacity(T, E, cfg.moe_top_k,
                                  cfg.moe_capacity_factor)
    r = moe_utils.top_k_routing(logits, cfg.moe_top_k, C, axes=axes,
                                dtype=cd)
    dispatched = moe_utils.dispatch_tokens(xt.astype(cd), r.plan)
    if ep > 1:
        expert_in = moe_utils.all_to_all_dispatch(dispatched, "ep", ep)
    else:
        expert_in = dispatched                   # [E(=E_loc), C, d]
    h = jnp.einsum("ecd,edf->ecf", expert_in, w1.astype(cd)) \
        + b1[:, None, :].astype(cd)
    h = jax.nn.gelu(h)
    # b2 is replicated over mp while the matmul is a row-parallel
    # PARTIAL (w2 holds an ff/mp shard) that the caller psums: scale
    # the bias by 1/mp so the psum restores it exactly once — adding
    # it unscaled would count it mp times (it must ride inside the
    # expert buffer, not after the combine, because each token's bias
    # share is gate-weighted per selected expert)
    eout = jnp.einsum("ecf,efd->ecd", h, w2.astype(cd)) \
        + (b2[:, None, :] / cfg.mp).astype(cd)
    if ep > 1:
        eout = moe_utils.all_to_all_combine(eout, "ep", ep)
    out = moe_utils.combine_tokens(eout, r.plan)
    counts, dropped = r.plan.counts, r.plan.dropped
    if axes:
        counts = jax.lax.psum(counts, axes)
        dropped = jax.lax.psum(dropped, axes)
    stats = {"balance": r.balance_loss, "z": r.z_loss,
             "counts": counts, "dropped": dropped}
    return out.reshape(B, S, d), stats


def _block(x, lp, cfg: GPTConfig):
    """One transformer block on (possibly seq-sharded) activations.

    x: [B, S_loc, d] where S_loc = S/mp if sequence_parallel else S.
    Returns same shape. Partial row-parallel outputs are reduced with
    psum (dense) or psum_scatter (sequence parallel).
    """
    sp = cfg.sequence_parallel and cfg.mp > 1

    def reduce_mp(t):
        if cfg.mp == 1:
            return t
        if sp:
            return jax.lax.psum_scatter(t, "mp", scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(t, "mp")

    def gather_sp(t):
        if sp:
            return jax.lax.all_gather(t, "mp", axis=1, tiled=True)
        return t

    h = _layer_norm(x, lp["ln1_w"], lp["ln1_b"])
    h = gather_sp(h)                      # full seq into attention
    attn, b_o = _attention(h, lp["w_qkv"], lp["b_qkv"], lp["w_o"],
                           lp["b_o"], cfg)
    attn = reduce_mp(attn) + b_o.astype(attn.dtype)
    if cfg.fused_add_ln:
        from ..ops.pallas.layer_norm import add_ln
        h2, x = add_ln(x, attn.astype(x.dtype), lp["ln2_w"],
                       lp["ln2_b"])
    else:
        x = x + attn.astype(x.dtype)
        h2 = _layer_norm(x, lp["ln2_w"], lp["ln2_b"])
    aux = _zero_moe_stats(cfg)
    if cfg.moe_experts:
        h2 = gather_sp(h2)
        ff, aux = _moe_ffn(h2, lp["gate"], lp["w_fc1"], lp["b_fc1"],
                           lp["w_fc2"], lp["b_fc2"], cfg)
        ff = reduce_mp(ff)
        bias = 0.0
    else:
        h2 = gather_sp(h2)
        ff, b2 = _dense_ffn(h2, lp["w_fc1"], lp["b_fc1"], lp["w_fc2"],
                            lp["b_fc2"], cfg)
        ff = reduce_mp(ff)
        bias = b2.astype(ff.dtype)
    # NOTE r5: a delayed-add carry variant (ff residual pending in the
    # carry, folded into the next block's fused add+LN) measured 37.0k
    # vs 39.5k tok/s -- the doubled remat carry outweighs the saved
    # residual-add fusions. Keep the plain add.
    x = x + (ff + bias).astype(x.dtype)
    return x, aux


def _stage_forward(x, blocks_local, cfg: GPTConfig):
    """Run this pp rank's layers (scan over the stacked layer dim)."""
    if cfg.remat:
        # default: full per-block remat — recompute the whole block in
        # backward. (The plain dots-saveable policy keeps the [B,H,S,S]
        # attention logits per layer — ~1GB/layer at S=1024 — and OOMs a
        # 16GB chip; fused attention hides its internals from the policy,
        # so named no-batch-dims policies are safe to try via
        # cfg.remat_policy.)
        policy = None
        if cfg.remat_policy == "save_splash_residuals":
            # keep the splash kernel's (out, logsumexp) residuals across
            # the backward: the block still fully remats (LN/FFN/matmuls
            # recompute) but the attention forward does NOT re-run — its
            # fused bwd kernel reads the saved residuals directly.
            # +~66MB/layer at [32,16,1024,64] bf16 for -1 splash fwd pass
            from ..ops.pallas.flash_attention import SPLASH_RESIDUAL_NAME
            policy = jax.checkpoint_policies.save_only_these_names(
                SPLASH_RESIDUAL_NAME)
        elif cfg.remat_policy is not None:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy)
        block_fn = jax.checkpoint(lambda c, p: _block(c, p, cfg),
                                  policy=policy)
    else:
        block_fn = lambda c, p: _block(c, p, cfg)  # noqa: E731

    if cfg.unroll_layers:
        n = jax.tree_util.tree_leaves(blocks_local)[0].shape[0]
        aux_tot = _zero_moe_stats(cfg)
        for i in range(n):
            lp = jax.tree_util.tree_map(lambda a: a[i], blocks_local)
            x, aux = block_fn(x, lp)
            aux_tot = jax.tree.map(jnp.add, aux_tot, aux)
        return x, aux_tot

    def body(carry, lp):
        y, aux = block_fn(carry, lp)
        return y, aux
    x, auxs = jax.lax.scan(body, x, blocks_local)
    return x, jax.tree.map(lambda a: jnp.sum(a, axis=0), auxs)


def _vocab_parallel_embed(tokens, tok_emb_local, cfg: GPTConfig):
    """c_embedding parity: rows sharded over mp; out-of-shard rows
    contribute 0 and psum assembles the full embedding."""
    V_loc = tok_emb_local.shape[0]
    if cfg.mp == 1:
        return jnp.take(tok_emb_local, tokens, axis=0)
    rank = jax.lax.axis_index("mp")
    start = rank * V_loc
    local = tokens - start
    ok = (local >= 0) & (local < V_loc)
    emb = jnp.take(tok_emb_local, jnp.clip(local, 0, V_loc - 1), axis=0)
    emb = jnp.where(ok[..., None], emb, 0.0)
    return jax.lax.psum(emb, "mp")


def _ce_sum_fused(y, head_local, labels, cfg: GPTConfig):
    """mp=1 fused softmax-CE (sum) with a custom vjp.

    The reference's `c_softmax_with_cross_entropy` / Megatron fused CE
    capability, TPU-style: logits stay in compute dtype (bf16) and are
    NEVER saved — the fp32 upcast feeds only the logsumexp/gather
    *reductions* (XLA fuses the convert into them, so no fp32 [B,S,V]
    buffer materialises), and the backward recomputes the bf16 logits
    from (y, head) with one extra head matmul. Residuals are just
    (yc, hc, lse, labels): the head's ~6.6GB fp32 logits highwater at
    [32,1024,50304] drops to a transient bf16 3.3GB, which is what buys
    the memory for the save_splash_residuals remat policy."""
    cd = cfg.compute_dtype
    y_dt, h_dt = y.dtype, head_local.dtype

    def _logits(yc, hc):
        return jnp.einsum("bsd,dv->bsv", yc, hc,
                          preferred_element_type=cd)

    @jax.custom_vjp
    def ce(y, head, labels):
        return _fwd(y, head, labels)[0]

    def _fwd(y, head, labels):
        logits = _logits(y.astype(cd), head.astype(cd))
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        tgt = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
        # residuals are (y, head, lse, labels): y and head are alive in
        # the caller anyway (no extra buffer), the bf16 casts + logits
        # recompute in _bwd
        return jnp.sum(lse - tgt), (y, head, lse, labels)

    def _bwd(res, g):
        y, head, lse, labels = res
        yc, hc = y.astype(cd), head.astype(cd)
        logits = _logits(yc, hc)
        # d/dlogits = softmax - onehot
        probs = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        dlogits = (g * (probs - oh)).astype(cd)
        dy = jnp.einsum("bsv,dv->bsd", dlogits, hc,
                        preferred_element_type=jnp.float32)
        dw = jnp.einsum("bsd,bsv->dv", yc, dlogits,
                        preferred_element_type=jnp.float32)
        return (dy.astype(y_dt), dw.astype(h_dt),
                np.zeros(labels.shape, jax.dtypes.float0))

    ce.defvjp(_fwd, _bwd)
    return ce(y, head_local, labels)


def _ce_sum(y, head_local, labels, cfg: GPTConfig):
    """Sum (not mean) of token CE over y [B,S',d]."""
    V_loc = head_local.shape[1]
    if cfg.mp == 1 and cfg.fused_ce:
        return _ce_sum_fused(y, head_local, labels, cfg)
    logits = jnp.einsum("bsd,dv->bsv", y.astype(cfg.compute_dtype),
                        head_local.astype(cfg.compute_dtype),
                        preferred_element_type=jnp.float32)
    if cfg.mp == 1:
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None],
                                  axis=-1)[..., 0]
        return jnp.sum(lse - tgt)
    rank = jax.lax.axis_index("mp")
    start = rank * V_loc
    # stable global logsumexp
    local_max = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    gmax = jax.lax.pmax(local_max, "mp")
    sumexp = jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1)
    Z = jax.lax.psum(sumexp, "mp")
    lse = jnp.log(Z) + gmax
    local_lab = labels - start
    ok = (local_lab >= 0) & (local_lab < V_loc)
    tgt_local = jnp.take_along_axis(
        logits, jnp.clip(local_lab, 0, V_loc - 1)[..., None], axis=-1)[..., 0]
    tgt = jax.lax.psum(jnp.where(ok, tgt_local, 0.0), "mp")
    return jnp.sum(lse - tgt)


def _vocab_parallel_ce(y, head_local, labels, cfg: GPTConfig):
    """c_softmax_with_cross_entropy parity. y [B,S,d] full seq; head_local
    [d, V/mp]; labels [B,S]. Returns mean loss (replicated over mp).

    ce_seq_chunks > 1 streams the head matmul + CE over sequence chunks
    (lax.map + per-chunk remat) so the fp32 [B,S,V] logits never fully
    materialise — the backward recomputes each chunk's logits."""
    B, S, _ = y.shape
    C = max(1, cfg.ce_seq_chunks)
    if C == 1 or S % C != 0:
        return _ce_sum(y, head_local, labels, cfg) / (B * S)
    Sc = S // C
    yc = jnp.swapaxes(y.reshape(B, C, Sc, -1), 0, 1)      # [C,B,Sc,d]
    lc = jnp.swapaxes(labels.reshape(B, C, Sc), 0, 1)     # [C,B,Sc]

    def chunk(args):
        yy, ll = args
        return _ce_sum(yy, head_local, ll, cfg)

    sums = jax.lax.map(jax.checkpoint(chunk), (yc, lc))
    return jnp.sum(sums) / (B * S)


# ------------------------------------------------------- pipeline + loss


def _loss_fn(params, tokens, labels, cfg: GPTConfig, dp_mean=True):
    """Per-device (inside shard_map) pipelined forward loss.

    tokens/labels: [B_local, S] (dp-sharded batch, full on this stage).
    GPipe schedule over cfg.micro_batches microbatches with ppermute.
    dp_mean=False returns the LOCAL shard's loss (no dp pmean) — the
    bucketed-grad path differentiates that per device and does the dp
    reduction itself, bucket by bucket.
    """
    pp, M = cfg.pp, cfg.micro_batches
    B_loc, S = tokens.shape
    assert B_loc % M == 0, "local batch must divide micro_batches"
    Bm = B_loc // M
    d = cfg.d_model
    sp = cfg.sequence_parallel and cfg.mp > 1
    S_loc = S // cfg.mp if sp else S
    cd = cfg.compute_dtype

    tok_m = tokens.reshape(M, Bm, S)
    lab_m = labels.reshape(M, Bm, S)
    T = M + pp - 1
    # tick t: stage0 consumes micro t (t < M); last stage finishes micro
    # t-(pp-1)
    pad_tok = jnp.zeros((T - M, Bm, S), tok_m.dtype)
    tok_sched = jnp.concatenate([tok_m, pad_tok], axis=0)
    pad_lab = jnp.zeros((T - M, Bm, S), lab_m.dtype)
    lab_sched = jnp.concatenate([jnp.zeros((pp - 1, Bm, S), lab_m.dtype),
                                 lab_m], axis=0)[:T]

    stage = jax.lax.axis_index("pp") if pp > 1 else 0
    is_first = stage == 0
    is_last = stage == pp - 1

    pos = params["pos_emb"][:S].astype(cd)

    def embed(tok):
        e = _vocab_parallel_embed(tok, params["tok_emb"], cfg).astype(cd)
        e = e + pos[None]
        if sp:
            rank = jax.lax.axis_index("mp")
            e = jax.lax.dynamic_slice_in_dim(e, rank * S_loc, S_loc, axis=1)
        return e

    def head_loss(y, lab_t):
        """Final LN + vocab head + CE — the O(B·S·d·V) matmul."""
        yl = _layer_norm(y, params["ln_f_w"], params["ln_f_b"])
        if sp:
            yl = jax.lax.all_gather(yl, "mp", axis=1, tiled=True)
        return _vocab_parallel_ce(yl, params["head"], lab_t, cfg)

    def tick(carry, xs):
        x_recv, loss_sum, aux_sum, n_done = carry
        tok_t, lab_t, t = xs
        if pp > 1:
            # lax.cond (not where): the embedding psum and especially the
            # [B,S,d]x[d,V] head matmul must only RUN on the stage that
            # needs them — at pp=4 and real vocab sizes the discarded head
            # matmuls would be a large pure-waste cost per tick. The
            # predicates are uniform across each mp group (same pp stage,
            # same tick), so the mp collectives inside the branches are
            # deadlock-free.
            x_in = jax.lax.cond(
                is_first, lambda: embed(tok_t).astype(x_recv.dtype),
                lambda: x_recv)
        else:
            x_in = embed(tok_t)
        y, aux = _stage_forward(x_in, params["blocks"], cfg)
        # this stage holds a REAL microbatch only for ticks in
        # [stage, stage+M); bubble ticks process padding and must not
        # contribute to the MoE losses or expert counts
        stage_valid = jnp.logical_and(t - stage >= 0, t - stage < M) \
            if pp > 1 else jnp.asarray(True)
        aux = jax.tree.map(
            lambda a: jnp.where(stage_valid, a, jnp.zeros_like(a)), aux)
        # pass activations down the pipe (circular; stage0's recv is unused)
        if pp > 1:
            x_next = jax.lax.ppermute(
                y, "pp", [(i, (i + 1) % pp) for i in range(pp)])
        else:
            x_next = y
        # last stage only: head + CE when a real micro has arrived
        if pp > 1:
            valid = jnp.logical_and(is_last, t >= pp - 1)
            loss_t = jax.lax.cond(
                valid, lambda: head_loss(y, lab_t),
                lambda: jnp.zeros((), jnp.float32))
        else:
            valid = t >= 0
            loss_t = head_loss(y, lab_t)
        loss_sum = loss_sum + jnp.where(valid, loss_t, 0.0)
        aux_sum = jax.tree.map(jnp.add, aux_sum, aux)
        n_done = n_done + jnp.where(valid, 1.0, 0.0)
        return (x_next, loss_sum, aux_sum, n_done), None

    x0 = jnp.zeros((Bm, S_loc, d), cd)
    (xf, loss_sum, aux_sum, n_done), _ = jax.lax.scan(
        tick, (x0, jnp.zeros((), jnp.float32), _zero_moe_stats(cfg),
               jnp.zeros((), jnp.float32)),
        (tok_sched, lab_sched, jnp.arange(T)))

    # average loss over microbatches; broadcast from last stage over pp
    loss = loss_sum / jnp.maximum(n_done, 1.0)
    if pp > 1:
        loss = jax.lax.psum(
            jnp.where(is_last, loss, 0.0), "pp")
    # MoE aux losses: each stage accumulated its local layers' stats
    # over its M valid ticks; psum over pp totals all layers. Balance/z
    # normalize to per-layer-per-micro; counts/dropped stay raw totals
    # for this step (already psum'd over the dp x ep token axes inside
    # `_moe_ffn`, so they are the GLOBAL step totals, replicated).
    stats = None
    if cfg.moe_experts:
        stats = aux_sum
        if pp > 1:
            stats = jax.lax.psum(stats, "pp")
        per = cfg.n_layers * max(M, 1)
        stats = dict(stats, balance=stats["balance"] / per,
                     z=stats["z"] / per)
        loss = loss + cfg.moe_aux_weight * stats["balance"] \
            + cfg.moe_z_weight * stats["z"]
    # mean over the data axes (each dp x ep rank computed its shard's
    # loss; the MoE stats are already axis-invariant)
    daxes = _moe_data_axes(cfg)
    if daxes and dp_mean:
        loss = jax.lax.pmean(loss, daxes)
    if cfg.moe_experts:
        return loss, stats
    return loss


# ------------------------------------------- bucketed DP grad reduction


def grad_bucket_count(params, bucket_bytes, grad_dtype=None):
    """Host-side mirror of `_bucketed_psum`'s bucket plan: per dtype,
    ceil(total_elems / elems_per_bucket). The overlap_smoke HLO contract
    checks the compiled step against exactly this number."""
    per_dtype = {}
    for leaf in jax.tree.leaves(params):
        dt = jnp.dtype(grad_dtype) if grad_dtype is not None \
            else jnp.dtype(leaf.dtype)
        if not jnp.issubdtype(dt, jnp.inexact):
            continue
        per_dtype[str(dt)] = per_dtype.get(str(dt), 0) + int(
            np.prod(leaf.shape))
    n = 0
    for dt, elems in per_dtype.items():
        per = max(1, int(bucket_bytes) // jnp.dtype(dt).itemsize)
        n += -(-elems // per)
    return n


def _bucketed_psum(grads, bucket_bytes, axis="dp"):
    """Reduce a pytree of per-device partial grads with ONE lax.psum per
    <= bucket_bytes flat bucket per dtype (instead of one per leaf).

    Bucket k+1's payload is optimization_barrier-chained on bucket k's
    result: XLA cannot re-combine the all-reduces into one op (which
    would undo the bucketing and its overlap) and must issue them in
    order — backward-completion order, since the flat layout follows
    the (reversed) leaf order. Returns (reduced_grads, n_buckets);
    n_buckets is static, = `grad_bucket_count`."""
    leaves, tree = jax.tree.flatten(grads)
    by_dtype = {}
    for i, g in enumerate(leaves):
        if jnp.issubdtype(g.dtype, jnp.inexact):
            by_dtype.setdefault(str(g.dtype), []).append(i)
    out = list(leaves)
    n_buckets = 0
    for dt, idxs in by_dtype.items():
        # reversed leaf order ~ backward completion order (the head /
        # late layers' grads retire first)
        idxs = list(reversed(idxs))
        flat = jnp.concatenate([leaves[i].ravel() for i in idxs]) \
            if len(idxs) > 1 else leaves[idxs[0]].ravel()
        per = max(1, int(bucket_bytes) // jnp.dtype(dt).itemsize)
        nb = -(-int(flat.shape[0]) // per)
        pieces, prev = [], None
        for k in range(nb):
            chunk = flat[k * per:(k + 1) * per]
            if prev is not None:
                chunk, _ = jax.lax.optimization_barrier((chunk, prev))
            red = jax.lax.psum(chunk, axis)
            pieces.append(red)
            prev = red
        n_buckets += nb
        red_flat = jnp.concatenate(pieces) if len(pieces) > 1 \
            else pieces[0]
        off = 0
        for i in idxs:
            sz = int(np.prod(leaves[i].shape))
            out[i] = red_flat[off:off + sz].reshape(leaves[i].shape)
            off += sz
    return jax.tree.unflatten(tree, out), n_buckets


# ------------------------------------------------------------ optimizer
#
# Gradients are taken OUTSIDE the loss shard_map (jax.value_and_grad of the
# shard_map'ed loss): shard_map's transpose machinery then inserts the
# correct cross-replica psums for every replicated leaf (verified: grads of
# replicated params used before column-parallel matmuls are WRONG if
# jax.grad runs inside shard_map with check_vma=False, and correct outside
# — see tests/test_hybrid_gpt.py). The optimizer update below therefore
# operates on full logical grads at the jit level; ZeRO sharding is
# expressed with GSPMD sharding constraints (the all-gather that
# group_sharded stage1/2 does by hand falls out of the constraint).


def _world_axes(cfg: GPTConfig):
    axes = []
    if cfg.dp > 1:
        axes.append("dp")
    if cfg.pp > 1:
        axes.append("pp")
    if cfg.mp > 1:
        axes.append("mp")
    if cfg.ep > 1:
        axes.append("ep")
    return tuple(axes)


def _zero_pad(cfg, n):
    from .zero import pad_len
    return pad_len(n, max(cfg.dp * cfg.pp * cfg.mp * cfg.ep, 1))


def init_opt_state(cfg: GPTConfig, params):
    """fp32 Adam moments. ZeRO (stage>=1): moments stored as a flat vector
    sharded over the whole device world (FSDP-style full sharding of
    optimizer state — the group_sharded stage1/2 capability)."""
    def per_leaf(p):
        if cfg.zero_stage >= 1:
            n = _zero_pad(cfg, p.size)
            return {"m": jnp.zeros((n,), jnp.float32),
                    "v": jnp.zeros((n,), jnp.float32)}
        return {"m": jnp.zeros(p.shape, jnp.float32),
                "v": jnp.zeros(p.shape, jnp.float32)}
    return jax.tree.map(per_leaf, params)


def opt_specs(cfg: GPTConfig, pspecs):
    def per_leaf(spec):
        if cfg.zero_stage >= 1:
            axes = _world_axes(cfg)
            # canonical form: P(), not P(None) — these leaves are
            # pinned as step out_shardings, where the two are
            # DIFFERENT jit-cache keys (analysis.specs, rule RH202)
            s = P(axes) if axes else P()
            return {"m": s, "v": s}
        return {"m": spec, "v": spec}
    return jax.tree.map(per_leaf, pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def _adam_update(cfg, p, g, m, v, lr, t, wd):
    b1, b2 = cfg.beta1, cfg.beta2
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    upd = mhat / (jnp.sqrt(vhat) + cfg.eps) + wd * p
    return p - lr * upd, m, v


def _apply_updates(cfg: GPTConfig, mesh, params, grads, opt_state, lr, t):
    """Logical-level Adam with optional ZeRO sharding constraints."""
    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_s = jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, dict) and "m" in x)
    axes = _world_axes(cfg)
    zshard = NamedSharding(mesh, P(axes if axes else None))
    new_p, new_s = [], []
    for p, g, s in zip(flat_p, flat_g, flat_s):
        g = g.astype(jnp.float32)
        wd = 0.0 if p.ndim <= 1 else cfg.weight_decay
        if cfg.zero_stage >= 1:
            n = p.size
            npad = _zero_pad(cfg, n)
            pf = jnp.pad(p.astype(jnp.float32).reshape(-1), (0, npad - n))
            gf = jnp.pad(g.reshape(-1), (0, npad - n))
            # constrain the update to run sharded over the world: XLA
            # reduce-scatters grads in and all-gathers params out (ZeRO).
            pf = jax.lax.with_sharding_constraint(pf, zshard)
            gf = jax.lax.with_sharding_constraint(gf, zshard)
            p2, m, v = _adam_update(cfg, pf, gf, s["m"], s["v"], lr, t, wd)
            new_p.append(p2[:n].reshape(p.shape).astype(p.dtype))
            new_s.append({"m": m, "v": v})
        else:
            p2, m, v = _adam_update(cfg, p.astype(jnp.float32), g,
                                    s["m"], s["v"], lr, t, wd)
            new_p.append(p2.astype(p.dtype))
            new_s.append({"m": m, "v": v})
    return (jax.tree.unflatten(tree, new_p),
            jax.tree.unflatten(tree, new_s))


# --------------------------------------------------------------- driver


def collective_bytes_per_step(cfg: GPTConfig, batch: int):
    """Analytic LOGICAL payload bytes per train step for the collectives
    GSPMD/shard_map compiles into the hybrid step (the compiled path
    fuses them into the executable, so the eager accounting in
    parallel/collective.py never sees them). Returns {label: bytes};
    wire bytes differ by the usual ring factors (all-reduce moves
    ~2(n-1)/n of payload over ICI). Single-chip configs (dp=pp=mp=1,
    zero off) honestly report no collective traffic."""
    d, L, S, V = cfg.d_model, cfg.n_layers, cfg.seq_len, cfg.vocab_size
    act_bytes = jnp.dtype(cfg.compute_dtype).itemsize
    n_params = 12 * L * d * d + V * d + S * d
    out = {}
    if cfg.mp > 1:
        # fwd: embedding psum + 2 psums/layer (attn out, mlp out) +
        # vocab-parallel CE psums; bwd mirrors them (x2)
        fwd = (2 * L + 1) * batch * S * d * act_bytes \
            + 3 * batch * S * 4
        out["mp_psum_est"] = 2 * fwd
    if cfg.dp > 1:
        g_bytes = act_bytes if cfg.bf16_grads else 4
        out["dp_grad_allreduce_est"] = n_params * g_bytes
    if cfg.pp > 1:
        # per-tick activation ppermute over the pp ring, fwd + bwd
        Bm = max(batch // max(cfg.micro_batches, 1), 1)
        out["pp_ppermute_est"] = (2 * cfg.micro_batches * cfg.pp
                                  * Bm * S * d * act_bytes)
    if cfg.moe_experts and cfg.ep > 1:
        # per layer: dispatch + combine all_to_all of the [E, C, d]
        # capacity tensors, fwd + bwd (x2 each)
        from . import moe_utils
        T_loc = max(batch // max(cfg.dp * cfg.ep, 1), 1) * S \
            // max(cfg.micro_batches, 1)
        C = moe_utils.expert_capacity(T_loc, cfg.moe_experts,
                                      cfg.moe_top_k,
                                      cfg.moe_capacity_factor)
        out["ep_alltoall_est"] = (4 * cfg.n_layers * cfg.micro_batches
                                  * cfg.moe_experts * C * d * act_bytes)
    if cfg.zero_stage >= 1 and cfg.dp * cfg.pp * cfg.mp * cfg.ep > 1:
        # optimizer update: grads reduce-scatter in, params all-gather
        # out, fp32 flat buffers; a world of 1 shards nothing
        out["zero_shard_est"] = 2 * n_params * 4
    return out


def auto_parallel_config(cfg: GPTConfig, n_devices, global_batch=32,
                         cluster=None, measurements=None):
    """Run the measurement-driven placement search (`auto_tuner.tune`)
    for this model and return (configured GPTConfig, TunedResult).

    The hybrid step's internal pipeline is the GPipe tick loop in
    `_loss_fn`, so the search prices schedules=("gpipe",); the
    zero-bubble schedule applies to `CompiledPipeline` models. The
    tuner's bucket_size maps onto `grad_bucket_bytes` only when the
    chosen mesh is pure dense DP (the config contract above)."""
    from . import auto_tuner
    cd_bytes = jnp.dtype(cfg.compute_dtype).itemsize
    mspec = auto_tuner.ModelSpec(
        n_layers=cfg.n_layers, d_model=cfg.d_model, seq_len=cfg.seq_len,
        vocab_size=cfg.vocab_size, d_ff=cfg.d_ff,
        global_batch=int(global_batch), n_heads=cfg.n_heads,
        param_bytes=4, grad_bytes=cd_bytes if cfg.bf16_grads else 4,
        act_bytes=cd_bytes, remat=cfg.remat,
        moe_experts=cfg.moe_experts, moe_top_k=cfg.moe_top_k,
        moe_capacity_factor=cfg.moe_capacity_factor)
    # zero_stages limited to what GPTConfig executes (0/1): clamping a
    # zero>=2 winner after the fact would run a config the search's
    # HBM-feasibility gate never admitted
    plan = auto_tuner.tune(mspec, cluster=cluster, n_devices=n_devices,
                           measurements=measurements,
                           schedules=("gpipe",), zero_stages=(0, 1))
    s = plan.strategy
    # the search only admits bucket_size>0 on pure dense-DP (ep=1)
    # meshes, so the scored config IS the executed one
    cfg = dataclasses.replace(
        cfg, dp=s.dp, mp=s.mp, pp=s.pp, ep=s.ep,
        micro_batches=s.micro_batches, zero_stage=s.zero_stage,
        grad_bucket_bytes=s.bucket_size)
    return cfg, plan


class HybridGPT:
    """Builds the mesh + ONE compiled hybrid train step.

    Usage:
        trainer = HybridGPT(cfg)
        params, opt = trainer.init(jax.random.PRNGKey(0))
        params, opt, loss = trainer.train_step(params, opt, tokens, labels)

    strategy="auto" (opt-in) replaces cfg's parallel dims with the
    auto_tuner's measurement-calibrated pick for `global_batch` before
    building; the chosen plan (incl. predicted MFU) is kept on
    `.tuner_plan` so callers can record prediction next to measurement.
    """

    def __init__(self, cfg: GPTConfig, devices=None, strategy=None,
                 global_batch=None, cluster=None, measurements=None):
        devices = devices if devices is not None else jax.devices()
        self.tuner_plan = None
        if strategy == "auto":
            cfg, self.tuner_plan = auto_parallel_config(
                cfg, n_devices=len(devices),
                global_batch=global_batch or 32, cluster=cluster,
                measurements=measurements)
        elif strategy is not None:
            raise ValueError(f"unknown strategy {strategy!r} "
                             "(None or 'auto')")
        self.cfg = cfg
        self.last_moe_stats = None
        self._moe_stats_pending = None
        n = cfg.dp * cfg.pp * cfg.mp * cfg.ep
        assert len(devices) >= n, \
            f"need {n} devices, have {len(devices)}"
        moe = cfg.moe_experts > 0
        # MoE configs ride a 4th "ep" mesh axis (present even at ep=1
        # so expert param specs always resolve and EP=1/EP=2 compile
        # identical program structure); dense configs keep the exact
        # 3-axis mesh — no new axis, no new compile cost. Tokens are
        # data-sharded over (dp, ep) jointly under MoE.
        if moe:
            shape, axes = (cfg.dp, cfg.pp, cfg.mp, cfg.ep), \
                ("dp", "pp", "mp", "ep")
        else:
            shape, axes = (cfg.dp, cfg.pp, cfg.mp), ("dp", "pp", "mp")
        self.mesh = Mesh(device_grid(devices[:n], shape), axes)
        self.pspecs = param_specs(cfg)
        self.ospecs = opt_specs(cfg, self.pspecs)
        cfg_ref = cfg
        mesh = self.mesh
        data_spec = P(("dp", "ep"), None) if moe else P("dp", None)
        self._data_spec = data_spec

        stats_spec = jax.tree.map(lambda _: P(), _zero_moe_stats(cfg))
        loss_out = (P(), stats_spec) if moe else P()
        loss_sm = _shard_map(
            lambda p, tok, lab: _loss_fn(p, tok, lab, cfg_ref),
            mesh=mesh, in_specs=(self.pspecs, data_spec, data_spec),
            out_specs=loss_out, check_vma=False)

        use_buckets = cfg.grad_bucket_bytes > 0 and cfg.dp > 1
        self._use_buckets = use_buckets
        if use_buckets:
            # grads taken INSIDE shard_map are the per-device partials
            # (no transpose psum) — exactly what the bucketed reduction
            # wants. Correct only because every leaf is dp-replicated
            # here (the pure dense-DP contract enforced by GPTConfig):
            # psum(d local-loss grads / dp) == grad of the dp-mean loss.
            def grads_body(p, tok, lab):
                def local_loss(pp_):
                    return _loss_fn(pp_, tok, lab, cfg_ref,
                                    dp_mean=False) / cfg_ref.dp
                loss, grads = jax.value_and_grad(local_loss)(p)
                loss = jax.lax.psum(loss, "dp")
                grads, _ = _bucketed_psum(grads,
                                          cfg_ref.grad_bucket_bytes)
                return loss, grads

            grads_sm = _shard_map(
                grads_body, mesh=mesh,
                in_specs=(self.pspecs, data_spec, data_spec),
                out_specs=(P(), self.pspecs), check_vma=False)

        def step(params, opt_state, tokens, labels, lr, t):
            mstats = None
            if cfg_ref.bf16_grads:
                cd = cfg_ref.compute_dtype
                target = jax.tree.map(
                    lambda a: a.astype(cd)
                    if a.dtype == jnp.float32 else a, params)
            else:
                target = params
            if use_buckets:
                loss, grads = grads_sm(target, tokens, labels)
            elif moe:
                (loss, mstats), grads = jax.value_and_grad(
                    loss_sm, has_aux=True)(target, tokens, labels)
            else:
                loss, grads = jax.value_and_grad(loss_sm)(target, tokens,
                                                          labels)
            if cfg_ref.grad_clip > 0:
                sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads))
                gnorm = jnp.sqrt(sq)
                scale = jnp.minimum(1.0, cfg_ref.grad_clip / (gnorm + 1e-6))
                grads = jax.tree.map(
                    lambda g: (g.astype(jnp.float32) * scale).astype(
                        g.dtype), grads)
            params, opt_state = _apply_updates(cfg_ref, mesh, params,
                                               grads, opt_state, lr, t)
            if moe:
                return params, opt_state, loss, mstats
            return params, opt_state, loss

        # pin the step outputs to the canonical param/opt shardings:
        # GSPMD otherwise infers spec-different-but-placement-identical
        # shardings for some leaves (P('pp', None) vs P('pp', 'mp') at
        # mp=1), so the SECOND step — fed the first step's outputs —
        # missed the jit cache and every trainer paid a double compile.
        # Specs go through analysis.specs.canonicalize_spec — the one
        # normal form init()/shard_data() ALSO place with, so the
        # out-pin and the initial device_put can never disagree on
        # cache identity (the repeated PR 7/8/10 hand-normalizations,
        # single-sourced).
        cn = lambda s: canonical_sharding(mesh, s)  # noqa: E731
        is_spec = lambda x: isinstance(x, P)       # noqa: E731
        out_shard = (jax.tree.map(cn, self.pspecs, is_leaf=is_spec),
                     jax.tree.map(cn, self.ospecs, is_leaf=is_spec),
                     cn(P()))
        step_shard = out_shard if not moe else out_shard + (
            jax.tree.map(lambda _: cn(P()), _zero_moe_stats(cfg)),)
        self._step = instrumented_jit(step, "HybridGPT.train_step",
                                      donate_argnums=(0, 1),
                                      out_shardings=step_shard)
        self._loss_sm = loss_sm
        self._loss_jit = instrumented_jit(loss_sm, "HybridGPT.loss")

        def steps_k(params, opt_state, tokens, labels, lr, t0, k):
            """K training steps as ONE executable (lax.scan over the
            step body) — the hapi run_many grouping applied to the
            hybrid trainer: amortizes per-dispatch host latency (not
            measured on the direct backend).
            MoE configs additionally stack the per-step routing stats
            as scan ys so train_many does not silently drop them."""
            def body(carry, i):
                p, o = carry
                res = step(p, o, tokens, labels, lr, t0 + i)
                ys = res[2] if not moe else (res[2], res[3])
                return (res[0], res[1]), ys
            (params, opt_state), ys = jax.lax.scan(
                body, (params, opt_state),
                jnp.arange(k, dtype=jnp.float32))
            if moe:
                losses, stats_k = ys
                return params, opt_state, losses, stats_k
            return params, opt_state, ys

        many_shard = out_shard if not moe else out_shard + (
            jax.tree.map(lambda _: cn(P()), _zero_moe_stats(cfg)),)
        self._steps_k = instrumented_jit(steps_k, "HybridGPT.train_many",
                                         static_argnums=(6,),
                                         donate_argnums=(0, 1),
                                         out_shardings=many_shard)

    def init(self, key):
        """Parameters and optimizer state, each leaf generated directly
        in its mesh sharding: no device ever holds the full model.
        (jax's threefry is partitionable, so the values do not depend
        on the mesh — the loss-parity tests across topologies rely on
        that.)"""
        def shardings(specs):
            return jax.tree.map(
                lambda s: canonical_sharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
        p_init = jax.jit(functools.partial(init_params, self.cfg),
                         out_shardings=shardings(self.pspecs))(key)
        o_init = jax.jit(functools.partial(init_opt_state, self.cfg),
                         out_shardings=shardings(self.ospecs))(p_init)
        return p_init, o_init

    def shard_data(self, tokens, labels):
        ds = canonical_sharding(self.mesh, self._data_spec)
        return (jax.device_put(tokens, ds), jax.device_put(labels, ds))

    def loss(self, params, tokens, labels):
        out = self._loss_jit(params, tokens, labels)
        return out[0] if self.cfg.moe_experts else out

    def loss_and_moe_stats(self, params, tokens, labels):
        """(loss, stats) for MoE configs — stats per `_zero_moe_stats`
        (balance/z per-layer-per-micro means, global expert counts and
        dropped-token total for the batch)."""
        assert self.cfg.moe_experts, "dense config has no MoE stats"
        return self._loss_jit(params, tokens, labels)

    def collective_bytes_per_step(self, batch):
        return collective_bytes_per_step(self.cfg, batch)

    def _record_collectives(self, tokens, steps=1, params=None):
        batch = int(tokens.shape[0])
        for label, nbytes in self.collective_bytes_per_step(batch).items():
            _metrics.COLLECTIVE_CALLS.labels(label).inc(steps)
            _metrics.COLLECTIVE_BYTES.labels(label).inc(nbytes * steps)
        if self._use_buckets and params is not None:
            gd = self.cfg.compute_dtype if self.cfg.bf16_grads else None
            _metrics.GRAD_BUCKETS.labels("compiled").set(
                grad_bucket_count(params, self.cfg.grad_bucket_bytes,
                                  gd))

    def train_step(self, params, opt_state, tokens, labels, lr=None,
                   step_num=1):
        lr = jnp.asarray(lr if lr is not None else self.cfg.learning_rate,
                         jnp.float32)
        t = jnp.asarray(step_num, jnp.float32)
        if _metrics._enabled:
            self._record_collectives(tokens, params=params)
        res = self._step(params, opt_state, tokens, labels, lr, t)
        if self.cfg.moe_experts:
            params, opt_state, loss, mstats = res
            # device arrays; host fetch deferred to the accessor. With
            # metrics on, record the PREVIOUS step's stats — step N is
            # already enqueued, so the device_get of step N-1's
            # (finished) stats never stalls async dispatch; the gauges
            # lag one step
            self.last_moe_stats = mstats
            if _metrics._enabled:
                prev = self._moe_stats_pending
                self._moe_stats_pending = mstats
                if prev is not None:
                    self._record_moe_stats(prev)
            return params, opt_state, loss
        return res

    def _record_moe_stats(self, mstats):
        st = jax.device_get(mstats)
        _metrics.record_moe_stats("train", st["counts"], st["dropped"],
                                  st["balance"])

    def flush_moe_metrics(self):
        """Drain the one-step-lagged MoE metrics (train_step records
        step N when step N+1 dispatches): call after the LAST step of
        a metrics-enabled run so the final step's routing stats land
        in the registry too."""
        if self._moe_stats_pending is not None and _metrics._enabled:
            self._record_moe_stats(self._moe_stats_pending)
        self._moe_stats_pending = None

    def train_many(self, params, opt_state, tokens, labels, k, lr=None,
                   start_step=1):
        """Run k steps in one device dispatch; returns
        (params, opt_state, losses[k]). MoE configs keep their
        routing stats: `last_moe_stats` holds the FINAL step's and the
        metrics record the k-step aggregate."""
        lr = jnp.asarray(lr if lr is not None else self.cfg.learning_rate,
                         jnp.float32)
        t0 = jnp.asarray(start_step, jnp.float32)
        if _metrics._enabled:
            self._record_collectives(tokens, steps=int(k), params=params)
        res = self._steps_k(params, opt_state, tokens, labels, lr, t0,
                            int(k))
        if self.cfg.moe_experts:
            params, opt_state, losses, stats_k = res
            self.last_moe_stats = jax.tree.map(lambda a: a[-1], stats_k)
            if _metrics._enabled:
                st = jax.device_get(stats_k)
                _metrics.record_moe_stats(
                    "train", np.sum(st["counts"], axis=0),
                    float(np.sum(st["dropped"])),
                    float(st["balance"][-1]))
            return params, opt_state, losses
        return res
