"""Block-table-native Pallas TPU kernels for paged attention.

The serving engine's three attention shapes — ragged chunked prefill
(one query per flat token), K-wide speculative verify (K consecutive
queries per slot) and K=1 decode — all reduce to ONE grouped pattern:
`G` queries that share a slot attend that slot's paged K/V at key
positions `<= their own`. The pure-XLA paths in
`ops.pallas.flash_attention` gather the slot's whole block list into a
contiguous `[S_max, H, Dh]` copy before attending; these kernels never
materialize that copy. Instead the grid iterates the
`[max_slots, max_blocks]` block tables directly:

* the block tables, owning-slot ids and per-query positions ride in
  **scalar memory** (`pltpu.PrefetchScalarGridSpec`), so each grid
  step's KV tile address is computed from the table BEFORE the body
  runs and Pallas double-buffers the `[block_size, H, Dh]` tile fetch
  against compute;
* the body runs **online softmax** (running max / denominator /
  weighted accumulator in VMEM scratch) over one KV block per grid
  step — peak live KV is one tile per buffer, not one sequence;
* **per-slot context-length masking** zeroes keys past the query's
  position, which also guarantees the NULL block's garbage and the
  unwritten tail of the newest block are never read through;
* KV tiles past the query group's last needed block are skipped with
  `pl.when` (the grid is rectangular over `max_blocks`, real work is
  ragged).

Quantized pools: with `k_scale`/`v_scale` (`[NB, BS, H]` fp32,
per-pool-entry-per-head — see `serving.kv_cache.PagedKVCache`), the
K/V tiles arrive int8 and are dequantized INSIDE the kernel right
after the tile load; the scale tiles ride the same block-table index
maps as the pools, so quantization adds two small scalar-indexed
fetches and two VPU multiplies per tile and nothing else changes.

The XLA gather paths stay the CPU parity oracles and the
`PADDLE_TPU_PAGED_PALLAS=0` fallback; `tests/test_paged_kernels.py`
runs every (shape x dtype) cell of this module against them in
interpret mode.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune

# finite mask value: -inf would NaN the running-max rescale on fully
# masked tiles (exp(-inf - -inf)); matches jax's paged kernel choice
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max) / 1e6  # ~-3.4e32/1e6

# Set by tests to run the kernels in Pallas interpret mode on the CPU
# mesh (exercises the real block-table/scalar-prefetch plumbing
# without a TPU).
_INTERPRET = False


def _on_tpu_backend() -> bool:
    from ...core.place import on_tpu_backend
    return on_tpu_backend()


def pallas_killed() -> bool:
    """True when `PADDLE_TPU_PAGED_PALLAS=0` is set: the operator asked
    for the pure-XLA gather reference on EVERY paged-attention entry —
    including jax's library decode kernel, not just these kernels — so
    a Pallas miscompile can be ruled out with one env var."""
    return os.environ.get("PADDLE_TPU_PAGED_PALLAS", "1") == "0"


def paged_pallas_enabled(head_dim, block_size) -> bool:
    """Dispatch gate for the block-table-native kernels.

    Env kill-switch first (`PADDLE_TPU_PAGED_PALLAS=0` restores the
    XLA gather paths everywhere), then backend/shape: on a TPU backend
    the kernels want a lane-aligned head_dim and a sublane-aligned
    block size so KV tiles hit full (8/32 x 128) registers; under
    `_INTERPRET` (tests) any shape runs. The alignment predicate is
    `autotune.paged_alignment_ok` — the SAME source of truth the
    kernel tuner's candidate filters use, so a tuned candidate the
    serve-time gate would refuse cannot exist (ISSUE 11)."""
    if pallas_killed():
        return False
    if _INTERPRET:
        return True
    return (_on_tpu_backend()
            and autotune.paged_alignment_ok(head_dim, block_size))


def _group_positions(pos_ref, g, G):
    """The group's G query positions as a [G] vector. G is static and
    tiny (1, or draft_k+1), so per-element SMEM reads unroll."""
    return jnp.stack([pos_ref[g, j] for j in range(G)])


def _paged_attend_kernel(slot_ref, bt_ref, pos_ref, q_ref, k_ref, v_ref,
                         *rest, block_size, G, quantized):
    """One (group, kv-block) grid cell.

    Refs: scalar-prefetch (slots [N], block tables [S, MB], positions
    [N, G]); q tile [1, G, H, Dh]; k/v tiles [1, BS, H, Dh] (int8 when
    quantized, + [1, BS, H] fp32 scale tiles); out tile [1, G, H, Dh];
    scratch m/l [H, G] and acc [H, G, Dh] carried across the kv-block
    grid axis."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    g = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = _group_positions(pos_ref, g, G)            # [G] int32
    max_pos = pos[G - 1] if G > 1 else pos[0]
    # positions within a verify group ascend, but take the true max so
    # the skip never depends on that packing detail
    for j in range(G - 1):
        max_pos = jnp.maximum(max_pos, pos[j])

    @pl.when(b * block_size <= max_pos)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)             # [G, H, Dh]
        k = k_ref[0].astype(jnp.float32)             # [BS, H, Dh]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0].astype(jnp.float32)[..., None]
            v = v * vs_ref[0].astype(jnp.float32)[..., None]
        # [H, G, BS] logits: one MXU contraction per head over Dh
        s = jax.lax.dot_general(
            jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        key_pos = b * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_size), 1)           # [G, BS]
        keep = key_pos <= pos[:, None]               # [G, BS]
        s = jnp.where(keep[None], s, MASK_VALUE)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1)                  # [H, G]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # explicit zeroing: on an all-masked tile s == m_new == MASK
        # and exp(0) would otherwise count the mask as probability 1
        p = jnp.exp(s - m_new[..., None]) * keep[None].astype(jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jax.lax.dot_general(
                            p, jnp.swapaxes(v, 0, 1),
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))

    @pl.when(b == nb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]    # [H, G, 1]
        out = acc_ref[...] / l                           # [H, G, Dh]
        o_ref[0] = jnp.swapaxes(out, 0, 1).astype(o_ref.dtype)


def _paged_attend_grouped(q, k_pool, v_pool, block_tables, slot_ids,
                          positions, k_scale=None, v_scale=None, *,
                          scale=None, kernel_name="paged_ragged",
                          tuning=None):
    """Grouped block-table-native attention.

    q [N, G, H, Dh]; k_pool/v_pool [NB, BS, H, Dh]; block_tables
    [S, MB] int32; slot_ids [N] int32 (-1 = padding group); positions
    [N, G] int32. Optional k_scale/v_scale [NB, BS, H] fp32 dequantize
    int8 pools inside the kernel. Returns [N, G, H, Dh] in q.dtype.

    `kernel_name` keys the autotuner lookup: the tuned grid-layout
    config (`dimension_semantics` — whether Mosaic may treat the
    group axis as parallel) is resolved HERE, at trace time, so a
    cached winner costs one dict probe inside the one compile and
    nothing per step. The block-sparse decode entry ("paged_sparse",
    ISSUE 15) is this same kernel fed a SHORTENED per-slot block table
    — the table width IS the sparsity budget, so its cache bucket
    carries MB where the dense entries' buckets do not."""
    N, G, H, Dh = q.shape
    NB, BS = k_pool.shape[0], k_pool.shape[1]
    S, MB = block_tables.shape
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if kernel_name == "paged_sparse":
        bucket = autotune.shape_bucket(N, G, H, Dh, BS, MB)
    else:
        bucket = autotune.shape_bucket(N, G, H, Dh, BS)
    tuned = tuning if tuning is not None else autotune.kernel_config(
        kernel_name, bucket, k_pool.dtype, default=None) or {}
    dim_sem = tuned.get("dimension_semantics")
    compiler_params = None
    if dim_sem is not None:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=tuple(dim_sem))
    qs = (q.astype(jnp.float32) * scale).astype(
        q.dtype if q.dtype != jnp.float64 else jnp.float32)

    def pool_map(g, b, slots, bt, pos):
        # padding groups (slot -1) clamp to slot 0; their table entries
        # may be NULL — the position mask hides whatever is fetched
        return (bt[jnp.maximum(slots[g], 0), b], 0, 0, 0)

    def scale_map(g, b, slots, bt, pos):
        return (bt[jnp.maximum(slots[g], 0), b], 0, 0)

    in_specs = [
        pl.BlockSpec((1, G, H, Dh), lambda g, b, *_: (g, 0, 0, 0)),
        pl.BlockSpec((1, BS, H, Dh), pool_map),
        pl.BlockSpec((1, BS, H, Dh), pool_map),
    ]
    args = [qs, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, BS, H), scale_map),
                     pl.BlockSpec((1, BS, H), scale_map)]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, H, Dh),
                               lambda g, b, *_: (g, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, G), jnp.float32),
                        pltpu.VMEM((H, G), jnp.float32),
                        pltpu.VMEM((H, G, Dh), jnp.float32)],
    )
    kernel = functools.partial(
        _paged_attend_kernel, block_size=BS, G=G, quantized=quantized)
    extra = {}
    if compiler_params is not None:
        extra["compiler_params"] = compiler_params
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, G, H, Dh), q.dtype),
        interpret=_INTERPRET, name=kernel_name, **extra,
        cost_estimate=pl.CostEstimate(
            flops=4 * N * G * H * Dh * MB * BS,
            bytes_accessed=(2 * N * MB * BS * H * Dh
                            * k_pool.dtype.itemsize
                            + 2 * N * G * H * Dh * q.dtype.itemsize),
            transcendentals=N * G * H * MB * BS),
    )(slot_ids.astype(jnp.int32), block_tables.astype(jnp.int32),
      positions.astype(jnp.int32), *args)


# --------------------------------------------------------------- entries


def ragged_attend(q, k_pool, v_pool, block_tables, slot_ids, positions,
                  k_scale=None, v_scale=None, *, scale=None,
                  kernel_name="paged_ragged"):
    """Flat-token ragged paged attention (chunked prefill + plain
    decode): q [T, H, Dh], one G=1 group per flat token. Signature
    mirrors `flash_attention.ragged_paged_attention`. The sparse
    decode region passes `kernel_name="paged_sparse"` with its
    shortened tables so tuned configs resolve under the sparse key."""
    T = q.shape[0]
    out = _paged_attend_grouped(
        q[:, None], k_pool, v_pool, block_tables, slot_ids,
        positions.reshape(T, 1), k_scale, v_scale, scale=scale,
        kernel_name=kernel_name)
    return out[:, 0]


def verify_attend(q, k_pool, v_pool, block_tables, slot_ids, positions,
                  k_scale=None, v_scale=None, *, scale=None,
                  kernel_name="paged_verify"):
    """K-wide speculative verify: q [B, K, H, Dh], positions [B, K] —
    one G=K group per slot, ONE block-table walk per group."""
    return _paged_attend_grouped(
        q, k_pool, v_pool, block_tables, slot_ids, positions,
        k_scale, v_scale, scale=scale, kernel_name=kernel_name)


def decode_attend(q, k_pool, v_pool, block_tables, context_lens,
                  k_scale=None, v_scale=None, *, scale=None):
    """K=1 decode: q [B, H, Dh], one query per slot attending its first
    `context_lens[b]` cached tokens."""
    B = q.shape[0]
    positions = (context_lens.astype(jnp.int32) - 1).reshape(B, 1)
    out = _paged_attend_grouped(
        q[:, None], k_pool, v_pool, block_tables,
        jnp.arange(B, dtype=jnp.int32), positions,
        k_scale, v_scale, scale=scale, kernel_name="paged_decode")
    return out[:, 0]


# ----------------------------------------------------------- autotuning


def _synth_paged_inputs(N, G, H, Dh, BS, context_len, dtype, seed):
    """Deterministic synthetic pools/tables/queries for one paged
    shape bucket (the tuner's measurement workload). `dtype` is the
    POOL dtype: int8/float8_e4m3fn build quantized pools with
    per-entry-per-head fp32 scales (the `kv_dtype="int8"`/"fp8_e4m3"
    serving layouts) under fp32 queries; otherwise scales are None."""
    import numpy as np
    rng = np.random.RandomState(seed)
    mb = -(-int(context_len) // BS)
    NB = N * mb + 1
    dtype = np.dtype(dtype)
    quant = dtype.itemsize == 1       # int8 or a scaled fp8 format
    qdt = np.float32 if quant else dtype
    q = jnp.asarray(rng.randn(N, G, H, Dh).astype(qdt))
    if quant:
        if dtype == np.int8:
            kp = jnp.asarray(rng.randint(-127, 128, (NB, BS, H, Dh))
                             .astype(np.int8))
            vp = jnp.asarray(rng.randint(-127, 128, (NB, BS, H, Dh))
                             .astype(np.int8))
        else:
            # fp8: stay inside the e4m3 finite range (casts past 448
            # produce NaN, which would poison the parity oracle)
            kp = jnp.asarray(np.clip(rng.randn(NB, BS, H, Dh) * 100,
                                     -440, 440).astype(np.float32)
                             ).astype(dtype)
            vp = jnp.asarray(np.clip(rng.randn(NB, BS, H, Dh) * 100,
                                     -440, 440).astype(np.float32)
                             ).astype(dtype)
        ks = jnp.asarray((np.abs(rng.randn(NB, BS, H)) * 0.02
                          + 0.005).astype(np.float32))
        vs = jnp.asarray((np.abs(rng.randn(NB, BS, H)) * 0.02
                          + 0.005).astype(np.float32))
    else:
        kp = jnp.asarray(rng.randn(NB, BS, H, Dh).astype(dtype))
        vp = jnp.asarray(rng.randn(NB, BS, H, Dh).astype(dtype))
        ks = vs = None
    bt = jnp.asarray(
        1 + np.arange(N * mb, dtype=np.int32).reshape(N, mb))
    slots = jnp.arange(N, dtype=jnp.int32)
    pos = jnp.asarray(
        np.clip(context_len - 1 - np.arange(G)[::-1], 0,
                context_len - 1).astype(np.int32)[None].repeat(N, 0))
    return q, kp, vp, bt, slots, pos, ks, vs


def tune_paged_kernel(kernel_name, N, G, H, Dh, BS, *,
                      context_len=None, dtype="float32", seed=0,
                      budget_s=None, timer=None, persist=True):
    """Search the grid-layout space of one paged-attention bucket.

    Candidates run the REAL block-table kernel (interpret mode off-TPU
    — the same plumbing tier-1 parity uses) against the XLA gather
    oracle; the winner lands in the persistent cache under
    `(kernel_name, shape_bucket(N, G, H, Dh, BS), dtype, backend)` so
    the serving engine's next trace picks it up for free."""
    import numpy as np
    from . import flash_attention as fa

    global _INTERPRET
    dtype = np.dtype(dtype)
    context_len = int(context_len or 4 * BS)
    args = _synth_paged_inputs(N, G, H, Dh, BS, context_len,
                               dtype, seed)

    def oracle(q, kp, vp, bt, slots, pos, ks, vs):
        if G == 1:
            return fa.ragged_gather_reference(q[:, 0], kp, vp, bt,
                                              slots, pos[:, 0], ks, vs)
        return fa.verify_gather_reference(q, kp, vp, bt, slots, pos,
                                          ks, vs)

    def build(cfg):
        def run(q, kp, vp, bt, slots, pos, ks, vs):
            out = _paged_attend_grouped(q, kp, vp, bt, slots, pos,
                                        ks, vs,
                                        kernel_name=kernel_name,
                                        tuning=cfg)
            return out[:, 0] if G == 1 else out
        return run

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        return autotune.search(
            kernel_name, autotune.shape_bucket(N, G, H, Dh, BS), dtype,
            autotune.paged_candidates(), build, args, oracle,
            rtol=2e-2, atol=2e-2, budget_s=budget_s, timer=timer,
            persist=persist,
            meta={"context_len": context_len, "seed": seed})
    finally:
        _INTERPRET = was


def tune_paged_sparse(N, G, H, Dh, BS, B, *, dtype="float32", seed=0,
                      budget_s=None, timer=None, persist=True):
    """Search the grid-layout space of the BLOCK-SPARSE decode bucket
    (ISSUE 15): the same grouped kernel fed a shortened `[N, B]` block
    table — the table width IS the sparsity budget, so the bucket key
    carries B (`shape_bucket(N, G, H, Dh, BS, B)`) and a tuned dense
    entry can never alias a sparse one. The measurement workload holds
    exactly B resident blocks per slot (context_len = B * BS), which
    is what the serving engine's compacted-position masking reduces
    the sparse region to."""
    import numpy as np
    from . import flash_attention as fa

    global _INTERPRET
    dtype = np.dtype(dtype)
    args = _synth_paged_inputs(N, G, H, Dh, BS, int(B) * BS,
                               dtype, seed)

    def oracle(q, kp, vp, bt, slots, pos, ks, vs):
        if G == 1:
            return fa.ragged_gather_reference(q[:, 0], kp, vp, bt,
                                              slots, pos[:, 0], ks, vs)
        return fa.verify_gather_reference(q, kp, vp, bt, slots, pos,
                                          ks, vs)

    def build(cfg):
        def run(q, kp, vp, bt, slots, pos, ks, vs):
            out = _paged_attend_grouped(q, kp, vp, bt, slots, pos,
                                        ks, vs,
                                        kernel_name="paged_sparse",
                                        tuning=cfg)
            return out[:, 0] if G == 1 else out
        return run

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        return autotune.search(
            "paged_sparse", autotune.shape_bucket(N, G, H, Dh, BS, B),
            dtype, autotune.paged_candidates(), build, args, oracle,
            rtol=2e-2, atol=2e-2, budget_s=budget_s, timer=timer,
            persist=persist, meta={"sparse_blocks": int(B),
                                   "seed": seed})
    finally:
        _INTERPRET = was


def tune_block_size(max_slots, H, Dh, *, context_len=64,
                    dtype="float32", seed=0, budget_s=None,
                    timer=None, persist=True):
    """Search the ENGINE-level KV block-size axis: each candidate
    re-shapes the pools (`NB = slots * ceil(ctx / BS) + 1`) and times
    decode-shaped ragged attention over them; parity holds per
    candidate against the gather oracle on the candidate's own pools.
    Candidates come from `autotune.paged_block_size_candidates` — the
    SAME alignment predicate as the serve-time dispatch gate, so the
    cached winner is admissible wherever the kernels are
    (`ServingEngine(block_size="auto")` reads the result)."""
    import numpy as np
    from . import flash_attention as fa

    global _INTERPRET
    dtype = np.dtype(dtype)

    def oracle(q, kp, vp, bt, slots, pos, ks, vs):
        return fa.ragged_gather_reference(q[:, 0], kp, vp, bt, slots,
                                          pos[:, 0], ks, vs)

    def build(cfg):
        bs = int(cfg["block_size"])
        cand_args = _synth_paged_inputs(max_slots, 1, H, Dh, bs,
                                        context_len, dtype, seed)

        def run(q, kp, vp, bt, slots, pos, ks, vs):
            if paged_pallas_enabled(Dh, bs):
                out = _paged_attend_grouped(q, kp, vp, bt, slots, pos,
                                            ks, vs,
                                            kernel_name="paged_decode")
                return out[:, 0]
            return fa.ragged_gather_reference(q[:, 0], kp, vp, bt,
                                              slots, pos[:, 0], ks, vs)
        return run, cand_args

    was = _INTERPRET
    if not _on_tpu_backend():
        _INTERPRET = True
    try:
        return autotune.search(
            "paged_block_size", autotune.shape_bucket(max_slots, H, Dh),
            dtype,
            autotune.paged_block_size_candidates(Dh, context_len),
            build, None, oracle, rtol=2e-2, atol=2e-2,
            budget_s=budget_s, timer=timer, persist=persist,
            meta={"context_len": int(context_len), "seed": seed})
    finally:
        _INTERPRET = was
