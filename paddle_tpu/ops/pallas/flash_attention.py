"""Flash attention as Pallas TPU kernels.

Parity: the reference's FlashAttention integration
(`paddle/phi/kernels/flash_attn_kernel.h`, `cmake/external/flashattn.cmake`,
`python/paddle/nn/functional/flash_attention.py:142`) — re-implemented as
TPU-native online-softmax kernels instead of the CUDA library.

Two tiers:

* `splash_mha` — the production path: jax's Pallas *splash attention*
  kernel (fwd + fused dkv/dq backward, causal block-skipping), tuned
  block sizes for v5e. Trace-measured 2.1x faster fwd+bwd than XLA's
  fused attention at [32,16,1024,64] and the engine behind the GPT
  training headline (see docs/gpt_perf_analysis.md). Off-TPU (the CPU
  test mesh) and for shapes `splash_supported` refuses it runs XLA's
  `jax.nn.dot_product_attention`; on a TPU that refusal warns.
* `flash_attention` — the hand-written educational fwd kernel kept for
  the paddle [B, S, H, D] API surface; backward recomputes in XLA.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


# ---------------------------------------------------------------------------
# splash attention (library Pallas kernel, fused backward) — production path
# ---------------------------------------------------------------------------

_SPLASH_CACHE = {}

# Set by tests to run the splash kernel in Pallas interpret mode on the
# CPU mesh (exercises the real mask/segment plumbing without a TPU).
_INTERPRET = False


def _on_tpu_backend() -> bool:
    if _INTERPRET:
        return True
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def splash_supported(seq_len: int, head_dim: int) -> bool:
    """Static gate for the splash kernel: a TPU backend (or the tests'
    interpret mode), a lane-aligned sequence and a head_dim the kernel
    tiles (multiples of 64; it pads them to the 128 lanes)."""
    return (_on_tpu_backend() and seq_len % 128 == 0
            and head_dim % 64 == 0 and seq_len >= 128)


def _splash_kernel(n_heads: int, seq_len: int, causal: bool,
                   segmented: bool = False,
                   residual_ckpt: str | None = None,
                   dtype: str = "float32", head_dim: int = 128):
    """Build (and cache) a vmapped splash kernel for [B, H, S, D] inputs.

    Block sizes: the largest power-of-two tile <= 1024 dividing S, with
    the fused dkv backward — measured fastest on v5e at S=1024 (5.0
    ms/layer fwd+bwd vs 10.6 for XLA's attention at [32,16,1024,64]).

    `segmented=True` builds the variant taking per-position segment ids
    (key-padding / ragged batches): position i attends j iff their
    segment ids match, fused into the same kernel (the TPU answer to the
    reference's varlen `flash_attn_unpadded` cu_seqlens path,
    `python/paddle/nn/functional/flash_attention.py:327`)."""
    import os
    block = next(b for b in (1024, 512, 256, 128) if seq_len % b == 0)
    # experiment override: "bq,bkv,bkvc,bqd,bkvd,bkvdc"
    env = os.environ.get("PADDLE_TPU_SPLASH_BLOCKS", "")
    # r5 in-model sweep at [32,16,1024,64] (tools/gpt_microbench.py):
    # fwd q-block 512 with full kv tiles but kv_compute 512, bwd
    # dq-block 512 / full kv — 836.5 vs 853.6 ms/step for the old
    # uniform-1024 fwd config; uniform 512 and q=256 were worse.
    # The autotuner ("splash" kernel space) supersedes the hand sweep
    # when a cached winner exists for the bucket; the env override
    # stays the top-priority experiment knob.
    bq = min(512, block)
    sizes = [bq, block, bq, bq, block, block]
    from . import autotune as _autotune
    _tuned = _autotune.kernel_config(
        "splash", _autotune.shape_bucket(seq_len, block, head_dim),
        dtype, default=None)
    if _tuned:
        sizes = [min(int(_tuned.get(k, s)), block) for k, s in zip(
            ("block_q", "block_kv", "block_kv_compute", "block_q_dkv",
             "block_kv_dkv", "block_kv_dkv_compute"), sizes)]
    key = (n_heads, seq_len, causal, block, segmented, residual_ckpt,
           env, tuple(sizes), _INTERPRET)
    if key not in _SPLASH_CACHE:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as smask)
        if env:
            parts = env.split(",")
            if len(parts) != 6:
                raise ValueError(
                    "PADDLE_TPU_SPLASH_BLOCKS wants 6 comma-separated "
                    "ints: bq,bkv,bkv_compute,bq_dkv,bkv_dkv,"
                    f"bkv_dkv_compute (got {env!r})")
            sizes = [min(int(x), block) for x in parts]
        bs = sk.BlockSizes(
            block_q=sizes[0], block_kv=sizes[1], block_kv_compute=sizes[2],
            block_q_dkv=sizes[3], block_kv_dkv=sizes[4],
            block_kv_dkv_compute=sizes[5],
            use_fused_bwd_kernel=True)
        m = (smask.CausalMask((seq_len, seq_len)) if causal
             else smask.FullMask((seq_len, seq_len)))
        mask = smask.MultiHeadMask([m] * n_heads)
        # the kernel object holds its mask tables as jnp arrays. This
        # runs inside whatever jit is tracing the caller, where jnp
        # constants are tracers — and the object is CACHED, so the next
        # jit to use it would meet another trace's tracers
        # (UnexpectedTracerError). Build it with concrete arrays.
        with jax.ensure_compile_time_eval():
            kern = sk.make_splash_mha(
                mask, head_shards=1, q_seq_shards=1, block_sizes=bs,
                interpret=_INTERPRET,
                residual_checkpoint_name=residual_ckpt)
        if segmented:
            _SPLASH_CACHE[key] = jax.vmap(
                lambda q, k, v, seg: kern(q, k, v, segment_ids=seg))
        else:
            _SPLASH_CACHE[key] = jax.vmap(kern)
    return _SPLASH_CACHE[key]


SPLASH_RESIDUAL_NAME = "splash_residuals"


def splash_mha(q, k, v, *, causal=True, scale=None, kv_keep=None,
               save_residuals_for_remat=False):
    """Multi-head self-attention on [B, H, S, D] tensors (q and k/v
    must share S — causal alignment for a shorter decode-style q is a
    different op; use the general masked path in
    `nn.functional.scaled_dot_product_attention` for KV-cache decode).

    `kv_keep`: optional [B, S] key-padding mask (nonzero = real token).
    Folded into the kernel as segment ids — real tokens are segment 1,
    padding segment 0, so real queries attend exactly the real keys.
    Padded query rows attend (only) other padded rows; their outputs are
    garbage by contract, exactly like the reference's varlen flash path
    where padded rows are never read back.

    `save_residuals_for_remat`: tag the kernel's saved residuals (out +
    logsumexp) with `checkpoint_name(SPLASH_RESIDUAL_NAME)` so a
    surrounding `jax.checkpoint(policy=save_only_these_names(
    SPLASH_RESIDUAL_NAME))` keeps them across the backward instead of
    re-running the attention forward during remat (the reference keeps
    softmax_lse for the same reason, `flash_attn_kernel.h:21`).

    TPU: splash Pallas kernel (fwd + fused backward). Off-TPU or for
    non-tileable shapes: XLA's fused attention. Differentiable either
    way."""
    b, h, s, d = q.shape
    if k.shape[2] != s or v.shape[2] != s:
        raise ValueError(
            f"splash_mha requires equal q/kv sequence lengths, got "
            f"q S={s}, k S={k.shape[2]}, v S={v.shape[2]}")
    if k.shape[1] != h or v.shape[1] != h:
        raise ValueError(
            f"splash_mha requires equal q/kv head counts (no GQA/MQA), "
            f"got q H={h}, k H={k.shape[1]}, v H={v.shape[1]}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if splash_supported(s, d):
        # a shape the gate accepts either runs the kernel or raises:
        # a trace-time refusal is a bug in the gate, not a fallback
        qs = (q * scale).astype(q.dtype)
        rc = SPLASH_RESIDUAL_NAME if save_residuals_for_remat else None
        if kv_keep is not None:
            from jax.experimental.pallas.ops.tpu.splash_attention \
                import splash_attention_kernel as sk
            seg = kv_keep.astype(jnp.int32)
            kern = _splash_kernel(h, s, causal, segmented=True,
                                  residual_ckpt=rc,
                                  dtype=str(q.dtype), head_dim=d)
            return kern(qs, k, v, sk.SegmentIds(q=seg, kv=seg))
        kern = _splash_kernel(h, s, causal, residual_ckpt=rc,
                              dtype=str(q.dtype), head_dim=d)
        return kern(qs, k, v)
    from . import xla_fallback
    xla_fallback("splash_mha", f"the gate refuses S={s}, head_dim={d} "
                 "(needs S % 128 == 0 and head_dim % 64 == 0)")
    mask = None
    if kv_keep is not None:
        mask = (kv_keep != 0)[:, None, None, :]  # [B, 1, 1(q), S]
    return jax.nn.dot_product_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), scale=scale, mask=mask,
        is_causal=causal).transpose(0, 2, 1, 3)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, block_k,
                seq_len):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, seq, d]; o_ref like q_ref
    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    q_idx = pl.program_id(1)
    q = q_ref[0] * scale  # [bq, d]

    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    q_start = q_idx * block_q
    if causal:
        num_k = jax.lax.div(q_start + block_q + block_k - 1, block_k)
    else:
        num_k = seq_len // block_k

    def body(ki, carry):
        m_prev, l_prev, acc_prev = carry
        k_start = ki * block_k
        k = k_ref[0, pl.ds(k_start, block_k), :]   # [bk, d]
        v = v_ref[0, pl.ds(k_start, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [bq, bk]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc_prev * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    """q/k/v: [BH, S, D] -> [BH, S, D]."""
    bh, s, d = q.shape
    grid = (bh, s // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_len=s)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        interpret=_INTERPRET, name="flash_fwd",
    )(q, k, v)


def _xla_reference(q, k, v, scale, causal):
    logits = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool))
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32)).astype(
        q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, scale, causal, block_q, block_k):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k)


def _flash_core_fwd(q, k, v, scale, causal, block_q, block_k):
    out = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v)


def _flash_core_bwd(scale, causal, block_q, block_k, res, g):
    # recompute-based backward in XLA (fused well by the compiler)
    q, k, v = res

    def f(q, k, v):
        return _xla_reference(q, k, v, scale, causal)
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    block_q=None, block_k=None):
    """q/k/v: [B, S, H, D] (paddle layout). bias unsupported -> caller
    falls back to the XLA path.

    `block_q`/`block_k` default to the autotuner's cached winner for
    this (S, D) shape bucket (`ops.pallas.autotune`, kernel
    ``flash_fwd``) and to the hand-picked 256/256 on a cache miss or
    with the kill-switch set; explicit arguments always win."""
    if bias is not None:
        raise NotImplementedError("flash_attention kernel: bias "
                                  "unsupported; use the XLA path")
    b, s, h, d = q.shape
    if block_q is None or block_k is None:
        from . import autotune
        tuned = autotune.kernel_config(
            "flash_fwd", autotune.shape_bucket(s, d), q.dtype,
            default=None) or {}

        def usable(v):
            # the pow2 bucket may cover sequences its winner doesn't
            # divide (S=768 in the 1024 bucket, winner 512): such a
            # tile would demote the shape to the XLA fallback, so the
            # hand default — which the pre-tuner path served — wins
            return v is not None and s % min(int(v), s) == 0

        tq, tk = tuned.get("block_q"), tuned.get("block_k")
        block_q = block_q or (tq if usable(tq) else DEFAULT_BLOCK_Q)
        block_k = block_k or (tk if usable(tk) else DEFAULT_BLOCK_K)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q != 0 or s % block_k != 0 or d % 128 != 0:
        # grid/num_k floor-divide by the block size: a non-divisible seq
        # would silently drop trailing queries/keys — refuse so the caller
        # falls back to the XLA path
        raise NotImplementedError(
            f"flash_attention kernel needs seq divisible by block "
            f"({block_q}/{block_k}) and head_dim%128==0 (got S={s}, D={d})")
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    out = _flash_core(to_bh(q), to_bh(k), to_bh(v), float(scale),
                      bool(causal), block_q, block_k)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)


def tune_flash(seq_len, head_dim, *, batch_heads=4, causal=True,
               dtype="float32", seed=0, budget_s=None, timer=None,
               persist=True):
    """Search the (block_q, block_k) space of the hand flash-forward
    kernel against the XLA softmax reference; the winner lands in the
    persistent cache so `flash_attention`'s next call resolves it for
    free (interpret mode off-TPU)."""
    import numpy as np

    from . import autotune

    global _INTERPRET
    dtype = np.dtype(dtype)
    rng = np.random.RandomState(seed)
    shape = (batch_heads, seq_len, head_dim)
    q = jnp.asarray(rng.randn(*shape).astype(dtype))
    k = jnp.asarray(rng.randn(*shape).astype(dtype))
    v = jnp.asarray(rng.randn(*shape).astype(dtype))
    scale = 1.0 / math.sqrt(head_dim)

    def oracle(q, k, v):
        return _xla_reference(q, k, v, scale, causal)

    def build(cfg):
        bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
        if seq_len % bq or seq_len % bk:
            return None

        def run(q, k, v):
            return _flash_fwd(q, k, v, scale, causal, bq, bk)
        return run

    was = _INTERPRET
    if not _on_tpu_backend() or _INTERPRET:
        _INTERPRET = True
    try:
        return autotune.search(
            "flash_fwd", autotune.shape_bucket(seq_len, head_dim),
            dtype, autotune.flash_candidates(seq_len, head_dim), build,
            (q, k, v), oracle, rtol=2e-2, atol=2e-2,
            budget_s=budget_s, timer=timer, persist=persist,
            meta={"causal": bool(causal), "seed": seed})
    finally:
        _INTERPRET = was


def tune_splash(seq_len, *, n_heads=2, batch=1, head_dim=128,
                causal=True, dtype="float32", seed=0, budget_s=None,
                timer=None, persist=True):
    """Search the six splash block sizes (fwd q/kv/kv_compute +
    fused-bwd dq/kv/kv_compute) against the XLA attention oracle.
    Candidates run the REAL library kernel — value AND input grads,
    so the backward block sizes are exercised too — in interpret mode
    off-TPU; the winner lands in the cache `_splash_kernel` resolves
    at build time."""
    import numpy as np

    from . import autotune

    dtype = np.dtype(dtype)
    block = next(b for b in (1024, 512, 256, 128)
                 if seq_len % b == 0)
    rng = np.random.RandomState(seed)
    shape = (batch, n_heads, seq_len, head_dim)
    q = jnp.asarray(rng.randn(*shape).astype(dtype))
    k = jnp.asarray(rng.randn(*shape).astype(dtype))
    v = jnp.asarray(rng.randn(*shape).astype(dtype))
    scale = 1.0 / math.sqrt(head_dim)

    def oracle(q, k, v):
        def f(q, k, v):
            out = jax.nn.dot_product_attention(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), scale=scale, is_causal=causal)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
        return (loss,) + grads

    interp = not _on_tpu_backend() or _INTERPRET

    def build(cfg):
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
            splash_attention_mask as smask)
        bs = sk.BlockSizes(
            block_q=cfg["block_q"], block_kv=cfg["block_kv"],
            block_kv_compute=cfg["block_kv_compute"],
            block_q_dkv=cfg["block_q_dkv"],
            block_kv_dkv=cfg["block_kv_dkv"],
            block_kv_dkv_compute=cfg["block_kv_dkv_compute"],
            use_fused_bwd_kernel=True)
        m = (smask.CausalMask((seq_len, seq_len)) if causal
             else smask.FullMask((seq_len, seq_len)))
        mask = smask.MultiHeadMask([m] * n_heads)
        kern = jax.vmap(sk.make_splash_mha(
            mask, head_shards=1, q_seq_shards=1, block_sizes=bs,
            interpret=interp))

        def run(q, k, v):
            def f(q, k, v):
                out = kern((q * scale).astype(q.dtype), k, v)
                return jnp.sum(out.astype(jnp.float32) ** 2)
            loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
                q, k, v)
            return (loss,) + grads
        return run

    return autotune.search(
        "splash", autotune.shape_bucket(seq_len, block, head_dim),
        dtype, autotune.splash_candidates(seq_len), build, (q, k, v),
        oracle, rtol=5e-2, atol=5e-2, budget_s=budget_s, timer=timer,
        persist=persist, meta={"causal": bool(causal), "seed": seed})


# ---------------------------------------------------------------------------
# paged attention (block-paged KV cache — the serving engine's kernel)
# ---------------------------------------------------------------------------


def _check_pool_heads(name, h_q, k_pool, v_pool, grouped=False):
    """Queries and pools must carry the SAME head count (`grouped`: the
    query heads a whole multiple of the pools'). Under tensor
    parallelism both are the per-shard slice (`H // tp`); a mismatch
    means a caller handed a sharded pool to unsharded queries (or vice
    versa), which the einsums would otherwise mis-broadcast into
    garbage attention instead of failing."""
    h_kv = k_pool.shape[-2]
    if grouped and v_pool.shape[-2] == h_kv and h_q % h_kv == 0:
        return
    if k_pool.shape[-2] != h_q or v_pool.shape[-2] != h_q:
        raise ValueError(
            f"{name}: q has {h_q} heads but k_pool/v_pool have "
            f"{k_pool.shape[-2]}/{v_pool.shape[-2]} — under tensor "
            "parallelism every operand must be the per-shard head "
            "slice (serving.distributed.tp_engine shards q and the "
            "pools together on the 'mp' axis)")


def _paged_kernel_enabled(head_dim, block_size, heads, quantized):
    """True -> the block-table-native kernel runs (and a Mosaic refusal
    raises); False -> the gather reference, said out loud when that
    happens on a TPU for any reason but the operator's kill-switch."""
    from . import paged_attention as _pk
    if _pk.paged_pallas_enabled(head_dim, block_size, heads, quantized):
        return True
    if not _pk.pallas_killed():
        from . import xla_fallback
        xla_fallback("paged_attention",
                     f"the gate refuses head_dim={head_dim}, "
                     f"block_size={block_size}, heads={heads}"
                     f"{' (quantized pools)' if quantized else ''} "
                     "(needs head_dim % 128 == 0, block_size % 8 == 0 "
                     "and, for quantized pools, block_size * heads % "
                     "128 == 0); the gather reference materialises "
                     "every slot's whole context per query")
    return False


def _gather_dequant(pool, scale_pool, bt, q_dtype):
    """pool[bt] as q.dtype, dequantized by the per-entry-per-head
    scales when the pool is int8 (`serving.kv_cache` layout:
    pool [NB, BS, H, Dh], scales [NB, BS, H])."""
    g = pool[bt].astype(q_dtype)
    if scale_pool is not None:
        g = g * scale_pool[bt].astype(q_dtype)[..., None]
    return g


def ragged_paged_attention(q, k_pool, v_pool, block_tables, slot_ids,
                           positions, k_scale=None, v_scale=None, *,
                           scale=None, kernel_name="paged_ragged",
                           runs=None, window=None, max_run=None,
                           layer=None, causal_block=None, select=None):
    """Flat-token attention over a block-paged KV cache — the kernel of
    the continuous-batching mixed step (`paddle_tpu.serving.engine`),
    following the Ragged-Paged-Attention shape discipline: ONE fixed
    `[T]` token axis carries an arbitrary mix of decode tokens and
    prefill chunks, so the compiled step never retraces as requests
    come and go.

    q            [T, H, Dh]  — one query per flat token
    k_pool/v_pool [NB, BS, H, Dh] — one layer's paged pools
    block_tables [S, MB] int32 — per-slot block lists, NULL-padded
    slot_ids     [T] int32 — owning slot per token (-1 = padding)
    positions    [T] int32 — token's position in its sequence

    Token t attends keys at positions <= positions[t] of its own slot
    (padding blocks beyond the sequence are masked by construction, so
    the NULL-block garbage is never read through).

    With `k_scale`/`v_scale` (`[NB, BS, H]` fp32) the pools are
    quantized (int8 or fp8_e4m3) and dequantized per entry per head —
    on the gather path right after the gather, in the Pallas kernels
    inside the KV tile load.

    `kernel_name` keys the autotuner lookup (the sparse decode region
    passes "paged_sparse" with its shortened block tables, ISSUE 15);
    the math is identical for any name.

    On a TPU backend (or under kernel-test interpret mode) this
    dispatches to the block-table-native Pallas kernel
    (`ops.pallas.paged_attention.ragged_attend`) — no gathered
    contiguous KV copy is ever materialized; `PADDLE_TPU_PAGED_PALLAS=0`
    or a CPU backend keeps the pure-XLA gather path below, which runs
    under JAX_PLATFORMS=cpu and is the parity oracle. The kernel walks
    each QUERY RUN's slot once (`paged_attention.paged_runs`: a decode
    token is a run of 1, a prefill chunk one run); `runs` hands it the
    runs of (slot_ids, positions) a caller already derived — the
    serving step does, once, outside its layer scan.

    Tensor parallelism: the TP serving engine
    (`serving.distributed.tp_engine`) calls this INSIDE shard_map with
    the head axis partitioned on `mp` — q and the pools both arrive as
    the per-shard head slice, and per-head attention needs no
    cross-shard communication. The head counts must agree, or the
    query heads be a multiple of the pools' (grouped queries: query
    head i reads KV head `i // (Hq // H)`).

    `window` (None = full attention) keeps keys `p - window < j <= p`
    for a query at p; table columns wholly behind a query's window are
    never read. `max_run` cuts the kernel's query runs (see
    `paged_attention.paged_runs`); the math does not depend on it.

    `causal_block` (None = causal; a power of two L): the mask is
    BLOCK-causal, as a model that decodes by diffusion over blocks is
    trained: positions are cut into blocks of L at multiples of L, and
    a query at p attends every key up to the end of its own block,
    `j <= p | (L - 1)`: causal between blocks, both ways inside one.
    No key past the last token of the query's run is attended (what
    lies there in the pool was not written by this sequence), so a run
    must end on a block boundary or where the sequence ends, whatever
    `max_run` cuts it into: the serving engine feeds whole blocks and
    cuts prefill chunks at multiples of L.

    `select` (None = every key the mask allows; bool `[T, >= MB *
    BS]`, a row a query, a column a key position): the query attends
    only the keys whose entry is True, of those the mask allows. The
    selection is made by the caller (a learned indexer's exact top-k:
    `ops.pallas.topk_select`) and applied here as data, on the kernel
    path as packed bits; `runs` may then leave runs out (a caller that
    attends its one-token runs over a gathered selection hands over
    the others), and the rows of no run leave as zeros.

    `layer` (None = the pools are one layer's, as above): the pools
    and scales are STACKED over layers, `[L, NB, BS, H, Dh]` and
    `[L, NB, BS, H]`, and layer `layer`'s blocks are read where they
    lie (`paged_attention.layer_blocks`: the flat view and the table
    offset, the same on the kernel and the gather path) — a layer scan
    passes its carried pools and its index, and no slice of a layer's
    pool is taken."""
    T, H, Dh = q.shape
    _check_pool_heads("ragged_paged_attention", H, k_pool, v_pool,
                      grouped=True)
    BS, Hkv = k_pool.shape[-3], k_pool.shape[-2]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if _paged_kernel_enabled(Dh, BS, Hkv, k_scale is not None):
        from .paged_attention import ragged_attend
        return ragged_attend(q, k_pool, v_pool, block_tables, slot_ids,
                             positions, k_scale, v_scale, scale=scale,
                             kernel_name=kernel_name, runs=runs,
                             window=window, max_run=max_run, layer=layer,
                             causal_block=causal_block, select=select)
    return ragged_gather_reference(q, k_pool, v_pool, block_tables,
                                   slot_ids, positions, k_scale,
                                   v_scale, scale=scale, window=window,
                                   layer=layer, causal_block=causal_block,
                                   max_run=max_run, select=select)


def ragged_gather_reference(q, k_pool, v_pool, block_tables, slot_ids,
                            positions, k_scale=None, v_scale=None, *,
                            scale=None, window=None, layer=None,
                            causal_block=None, max_run=None, select=None):
    """The pure-XLA gather implementation of `ragged_paged_attention`
    — the CPU path, the kernel-parity oracle, and the admission gate
    the autotuner holds every paged candidate against. `max_run` only
    matters with a `causal_block`: a query attends no key past its
    run's last token, and the runs are the kernel's."""
    from .paged_attention import (block_end, check_causal_block,
                                  layer_blocks, paged_runs)
    T, H, Dh = q.shape
    block_tables, (k_pool, v_pool, k_scale, v_scale) = layer_blocks(
        block_tables, layer, k_pool, v_pool, k_scale, v_scale)
    BS, Hkv = k_pool.shape[1], k_pool.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    safe_slot = jnp.clip(slot_ids, 0, block_tables.shape[0] - 1)
    bt = block_tables[safe_slot]                      # [T, MB]
    S = bt.shape[1] * BS
    k = _gather_dequant(k_pool, k_scale, bt, q.dtype).reshape(
        T, S, Hkv, Dh)
    v = _gather_dequant(v_pool, v_scale, bt, q.dtype).reshape(
        T, S, Hkv, Dh)
    reach = positions
    causal_block = check_causal_block(causal_block)
    if causal_block is not None:
        # the end of the query's block, and no further than its run
        _, start, length, _, first = paged_runs(slot_ids, positions,
                                                max_run)
        run = jnp.clip(jnp.searchsorted(start, jnp.arange(T), "right")
                       - 1, 0, T - 1)
        reach = jnp.minimum(block_end(positions, causal_block),
                            first[run] + length[run] - 1)
    keep = jnp.arange(S)[None, :] <= reach[:, None]       # [T, S]
    if window is not None:
        keep &= jnp.arange(S)[None, :] > positions[:, None] - window
    if select is not None:
        keep &= select[:, :S]
    if Hkv != H:
        # grouped queries: Gq query heads read each KV head
        qg = q.reshape(T, Hkv, H // Hkv, Dh)
        logits = jnp.einsum("thgd,tshd->thgs", qg, k).astype(
            jnp.float32) * scale
        logits = jnp.where(keep[:, None, None, :], logits, -1e9)
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("thgs,tshd->thgd", p, v).reshape(T, H, Dh)
    logits = jnp.einsum("thd,tshd->ths", q, k).astype(jnp.float32)
    logits = logits * scale
    logits = jnp.where(keep[:, None, :], logits, -1e9)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("ths,tshd->thd", p, v)


def verify_paged_attention(q, k_pool, v_pool, block_tables, slot_ids,
                           positions, k_scale=None, v_scale=None, *,
                           scale=None, kernel_name="paged_verify",
                           layer=None):
    """Verify-shaped paged attention: q `[B, K, H, Dh]` — K queries per
    slot (the speculative draft window: the last accepted token plus
    the proposed draft tokens), each attending its own slot's paged
    keys at positions <= its own.

    q            [B, K, H, Dh] — K consecutive queries per slot
    k_pool/v_pool [NB, BS, H, Dh] — one layer's paged pools
    block_tables [S, MB] int32 — per-slot block lists, NULL-padded
    slot_ids     [B] int32 — owning slot per query GROUP (-1 = padding)
    positions    [B, K] int32 — per-query positions in the sequence

    The row-granular sibling of `ragged_paged_attention`: the block
    table is gathered ONCE per slot instead of once per flat token, so
    the K-wide verify window costs one decode-shaped gather rather than
    K of them — this is the entry the serving engine's speculative
    mixed step uses for its fixed `[max_slots, K]` verify region.
    Causality across the window is the position mask itself: draft
    query j sees drafts 0..j-1 and nothing later, which is exactly the
    sequential-greedy semantics the verifier needs.

    On a TPU backend (or kernel-test interpret mode) this dispatches
    to the block-table-native Pallas kernel
    (`ops.pallas.paged_attention.verify_attend`); otherwise the
    pure-XLA gather path below is the CPU-safe parity oracle. With
    `k_scale`/`v_scale` the int8 pools dequantize per entry per head.
    Under tensor parallelism q and the pools are the per-shard head
    slice, and with `layer` the pools are stacked and read in place,
    like `ragged_paged_attention`."""
    B, K, H, Dh = q.shape
    _check_pool_heads("verify_paged_attention", H, k_pool, v_pool)
    BS = k_pool.shape[-3]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if _paged_kernel_enabled(Dh, BS, H, k_scale is not None):
        from .paged_attention import verify_attend
        return verify_attend(q, k_pool, v_pool, block_tables, slot_ids,
                             positions, k_scale, v_scale, scale=scale,
                             kernel_name=kernel_name, layer=layer)
    return verify_gather_reference(q, k_pool, v_pool, block_tables,
                                   slot_ids, positions, k_scale,
                                   v_scale, scale=scale, layer=layer)


def verify_gather_reference(q, k_pool, v_pool, block_tables, slot_ids,
                            positions, k_scale=None, v_scale=None, *,
                            scale=None, layer=None):
    """The pure-XLA gather implementation of `verify_paged_attention`
    (CPU path / parity oracle / tuner admission gate)."""
    from .paged_attention import layer_blocks
    B, K, H, Dh = q.shape
    block_tables, (k_pool, v_pool, k_scale, v_scale) = layer_blocks(
        block_tables, layer, k_pool, v_pool, k_scale, v_scale)
    BS = k_pool.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    safe_slot = jnp.clip(slot_ids, 0, block_tables.shape[0] - 1)
    bt = block_tables[safe_slot]                      # [B, MB]
    S = bt.shape[1] * BS
    k = _gather_dequant(k_pool, k_scale, bt, q.dtype).reshape(
        B, S, H, Dh)
    v = _gather_dequant(v_pool, v_scale, bt, q.dtype).reshape(
        B, S, H, Dh)
    logits = jnp.einsum("bkhd,bshd->bhks", q, k).astype(jnp.float32)
    logits = logits * scale
    keep = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    logits = jnp.where(keep[:, None], logits, -1e9)    # [B, H, K, S]
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhks,bshd->bkhd", p, v)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    k_scale=None, v_scale=None, *, scale=None):
    """Decode-shaped paged attention: q [B, H, Dh], one query per
    sequence, attending its first `context_lens[b]` cached tokens.

    On a TPU backend (or kernel-test interpret mode) this dispatches
    to our block-table-native Pallas kernel
    (`ops.pallas.paged_attention.decode_attend` — handles fp AND int8
    pools); everywhere else — CPU, shapes the gate refuses, or the
    `PADDLE_TPU_PAGED_PALLAS=0` kill-switch — the pure-XLA gather
    reference above runs. (jax's library paged kernel, the TPU path
    before the grouped kernel landed, accepted only a strict subset
    of the shapes our gate takes, so it can no longer be reached and
    was dropped.) Under tensor parallelism q and the pools are the
    per-shard head slice. `context_lens` must be >= 1 per row: an
    empty context has no defined attention output (the kernel yields
    ~0, the gather reference a uniform average — neither meaningful),
    and the serving engine never decodes an empty slot."""
    B, H, Dh = q.shape
    _check_pool_heads("paged_attention", H, k_pool, v_pool)
    BS = k_pool.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if _paged_kernel_enabled(Dh, BS, H, k_scale is not None):
        from .paged_attention import decode_attend
        return decode_attend(q, k_pool, v_pool, block_tables,
                             context_lens, k_scale, v_scale,
                             scale=scale)
    return ragged_paged_attention(
        q, k_pool, v_pool, block_tables,
        jnp.arange(B, dtype=jnp.int32),
        context_lens.astype(jnp.int32) - 1, k_scale, v_scale,
        scale=scale)
